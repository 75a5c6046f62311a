"""Paper Table 4: second model / second language (JaColBERTv2 analogue).

Hierarchical pooling on the Japanese-analogue corpora (longer docs,
doc_maxlen=160 vs 128, different vocab), 2-bit PLAID, Recall@5 —
swept through ``repro.eval.QualitySweep`` and the ``repro.Retriever``
facade; lands in the ``table4`` section of ``BENCH_quality.json``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import bench_encoder
from repro.eval import (BENCH_QUALITY_FILE, QualitySweep,
                        synthetic_dataset, write_bench_section)

DATASETS = ["jsquad", "miracl-ja"]
FACTORS = (1, 2, 3, 4, 6)
BACKEND = "plaid"
BITS = 2
METRIC = "recall@5"


def run(verbose: bool = True, out: str = BENCH_QUALITY_FILE):
    params, cfg = bench_encoder(ja=True, verbose=verbose)
    reports = {}
    for name in DATASETS:
        ds = synthetic_dataset(name, vocab_size=cfg.trunk.vocab_size,
                               doc_maxlen=cfg.doc_maxlen - 2,
                               query_maxlen=cfg.query_maxlen - 2,
                               n_docs=160, n_queries=20)
        reports[name] = QualitySweep(
            params, cfg, ds, methods=("ward",), factors=FACTORS,
            backends=(BACKEND,), quant_bits=(BITS,),
            metrics=(METRIC,)).run()

    print("\nTable 4 — hierarchical pooling, second model (JA analogue), "
          "relative Recall@5, 2-bit PLAID")
    print(f"{'f':>3s}" + "".join(f"{d:>12s}" for d in DATASETS)
          + f"{'avg':>10s}")
    avg = {}
    for f in FACTORS:
        if f == 1:
            continue
        vals = [reports[d].cell(BACKEND, "ward", f, BITS)
                .relative[METRIC] for d in DATASETS]
        avg[str(f)] = float(np.mean(vals))
        print(f"{f:3d}" + "".join(f"{v:12.2f}" for v in vals)
              + f"{np.mean(vals):10.2f}")
    write_bench_section(out, "table4",
                        {"reports": reports, "avg_relative": avg,
                         "backend": BACKEND, "quant_bits": BITS,
                         "metric": METRIC})
    return {"rows": reports, "avg": avg}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
