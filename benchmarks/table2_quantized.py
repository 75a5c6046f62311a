"""Paper Table 2 (+Fig 2): token pooling composed with 2-bit residual
quantization + PLAID staged search; BEIR-like + LoTTe-like datasets.

Cells come from ``repro.eval.QualitySweep`` through the ``repro.Retriever``
facade; per-dataset reports land in the ``table2`` section of
``BENCH_quality.json``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import bench_encoder
from repro.eval import (BENCH_QUALITY_FILE, QualitySweep,
                        synthetic_dataset, write_bench_section)

BEIR = ["scifact", "scidocs", "nfcorpus", "fiqa", "trec-covid", "touche"]
LOTTE = ["lotte-writing", "lotte-recreation", "lotte-lifestyle"]
METHODS = ("ward", "kmeans", "sequential")
FACTORS = (1, 2, 3, 4, 6)
BACKEND = "plaid"
BITS = 2


def run(verbose: bool = True, out: str = BENCH_QUALITY_FILE):
    params, cfg = bench_encoder(verbose=verbose)
    reports, metric_of = {}, {}
    for name in BEIR + LOTTE:
        metric = "ndcg@10" if name in BEIR else "success@5"
        metric_of[name] = metric
        ds = synthetic_dataset(name, vocab_size=cfg.trunk.vocab_size,
                               doc_maxlen=cfg.doc_maxlen - 2,
                               query_maxlen=cfg.query_maxlen - 2,
                               n_docs=160, n_queries=20)
        rep = QualitySweep(
            params, cfg, ds, methods=METHODS, factors=FACTORS,
            backends=(BACKEND,), quant_bits=(BITS,),
            metrics=(metric,)).run()
        reports[name] = rep
        if verbose:
            base = rep.baseline(BACKEND, BITS).metrics[metric]
            print(f"--- {name} [{metric}] baseline {base:.4f} ---")

    print("\nTable 2 — relative performance (100 = no pooling), "
          "2-bit PLAID")
    names = BEIR + LOTTE
    hdr = f"{'method':12s}{'f':>3s}" + "".join(
        f"{d[:9]:>11s}" for d in names) + f"{'avg':>8s}"
    print(hdr)
    avg = {}
    for m in METHODS:
        for f in FACTORS:
            if f == 1 or (m == "sequential" and f not in (2, 4)):
                continue
            vals = [reports[d].cell(BACKEND, m, f, BITS)
                    .relative[metric_of[d]] for d in names]
            avg[f"{m}@{f}"] = float(np.mean(vals))
            print(f"{m:12s}{f:3d}" + "".join(
                f"{v:11.2f}" for v in vals) + f"{np.mean(vals):8.2f}")
    write_bench_section(out, "table2",
                        {"reports": reports, "avg_relative": avg,
                         "backend": BACKEND, "quant_bits": BITS,
                         "metric_by_dataset": metric_of})
    return {"rows": reports, "avg": avg}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
