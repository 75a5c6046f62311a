"""Paper Table 1 (+Fig 1): token pooling on 16-bit vectors, HNSW index.

Relative NDCG@10 (100 = unpooled) for hierarchical/kmeans/sequential
pooling at factors 2/3/4/6, on the small BEIR-like datasets. Every cell
is produced by ``repro.eval.QualitySweep`` through the public
``repro.Retriever`` facade (corpus encoded once per dataset, baseline
built once), and the per-dataset reports land in the ``table1`` section
of ``BENCH_quality.json``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import bench_encoder
from repro.eval import (BENCH_QUALITY_FILE, QualitySweep,
                        synthetic_dataset, write_bench_section)

DATASETS = ["scifact", "scidocs", "nfcorpus", "fiqa"]
METHODS = ("ward", "kmeans", "sequential")
FACTORS = (1, 2, 3, 4, 6)
BACKEND = "hnsw"
METRIC = "ndcg@10"


def run(verbose: bool = True, out: str = BENCH_QUALITY_FILE):
    params, cfg = bench_encoder(verbose=verbose)
    reports = {}
    for name in DATASETS:
        ds = synthetic_dataset(name, vocab_size=cfg.trunk.vocab_size,
                               doc_maxlen=cfg.doc_maxlen - 2,
                               query_maxlen=cfg.query_maxlen - 2,
                               n_docs=150, n_queries=20)
        rep = QualitySweep(
            params, cfg, ds, methods=METHODS, factors=FACTORS,
            backends=(BACKEND,), metrics=(METRIC,),
            index_overrides={"hnsw_candidates": 384}).run(verbose=verbose)
        reports[name] = rep
        if verbose:
            base = rep.baseline(BACKEND).metrics[METRIC]
            print(f"--- {name} (baseline {METRIC} {base:.4f}) ---")
            print(rep.markdown_table(METRIC, backend=BACKEND))

    # paper-style summary: relative performance matrix
    print("\nTable 1 — relative NDCG@10 (100 = no pooling), "
          "16-bit HNSW")
    hdr = f"{'method':12s}{'f':>3s}" + "".join(
        f"{d[:8]:>10s}" for d in DATASETS) + f"{'avg':>10s}"
    print(hdr)
    avg = {}
    for m in METHODS:
        for f in FACTORS:
            if f == 1 or (m == "sequential" and f not in (2, 4)):
                continue
            vals = [reports[d].cell(BACKEND, m, f).relative[METRIC]
                    for d in DATASETS]
            avg[f"{m}@{f}"] = float(np.mean(vals))
            print(f"{m:12s}{f:3d}" + "".join(
                f"{v:10.2f}" for v in vals) + f"{np.mean(vals):10.2f}")
    write_bench_section(out, "table1",
                        {"reports": reports, "avg_relative": avg,
                         "backend": BACKEND, "metric": METRIC})
    return {"rows": reports, "avg": avg}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
