"""Index persistence benchmark: bytes on disk + cold-load latency.

    PYTHONPATH=src python benchmarks/persist_bench.py --docs 300

For each backend x pool_factor in {1, 2, 4}: encode + pool + build the
index, save the artifact, then measure

  * ``disk_bytes``        — real serialized payload size (the number the
                            paper's Table 3 talks about, finally on disk),
  * ``cold_load_ms``      — ``load(mmap=True)`` time: manifest parse +
                            mmap setup, no payload reads,
  * ``first_query_ms``    — the first search batch on the freshly loaded
                            index (faults the mapped payloads in and,
                            for plaid, decodes the reconstruction store),
  * ``warm_query_ms``     — the same batch once resident,

and emit ``BENCH_persist.json``. Build-from-scratch time is reported
alongside so the artifact's value is explicit: restart cost collapses
from re-encode+rebuild to cold_load + first_query.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time
from dataclasses import replace

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core.persist import artifact_bytes, load_index
from repro.data.corpus import DATASET_SPECS, SyntheticRetrievalCorpus
from repro.models.colbert import init_colbert
from repro.retrieval.indexer import Indexer
from repro.retrieval.searcher import Searcher


def bench_cell(params, cfg, corpus, backend: str, pool_factor: int,
               qs: np.ndarray, out_root: str, k: int, ndocs: int):
    toks = corpus.doc_token_batch(cfg.doc_maxlen - 2)
    art = os.path.join(out_root, f"{backend}_f{pool_factor}")
    t0 = time.time()
    from repro.core.spec import IndexSpec, PoolingSpec
    indexer = Indexer(
        params, cfg,
        index_spec=IndexSpec.from_config(cfg, backend=backend,
                                         ndocs=ndocs),
        pooling_spec=PoolingSpec(method="ward",
                                 factor=max(pool_factor, 1)))
    index, stats = indexer.build(toks, out_dir=art)
    build_s = time.time() - t0

    t0 = time.time()
    loaded = load_index(art, mmap=True)
    cold_load_s = time.time() - t0
    t0 = time.time()
    S1, I1 = loaded.search_batch(qs, k=k)
    first_query_s = time.time() - t0
    t0 = time.time()
    S2, I2 = loaded.search_batch(qs, k=k)
    warm_query_s = time.time() - t0
    assert np.array_equal(np.asarray(I1), np.asarray(I2))

    row = {
        "backend": backend, "pool_factor": pool_factor,
        "n_docs": stats.n_docs,
        "n_vectors_stored": stats.n_vectors_stored,
        "vector_reduction": stats.vector_reduction,
        "disk_bytes": artifact_bytes(art),
        "build_s": build_s,
        "cold_load_ms": cold_load_s * 1e3,
        "first_query_ms": first_query_s * 1e3,
        "warm_query_ms": warm_query_s * 1e3,
    }
    print(f"{backend:6s} f={pool_factor} "
          f"{row['disk_bytes'] / 2**20:8.2f} MiB  "
          f"build {build_s:6.1f}s  cold-load {row['cold_load_ms']:7.1f}ms  "
          f"first-query {row['first_query_ms']:7.1f}ms")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="scifact")
    ap.add_argument("--docs", type=int, default=300)
    ap.add_argument("--queries", type=int, default=16,
                    help="batch size of the cold/warm query measurement")
    ap.add_argument("--backends", default="flat,hnsw,plaid")
    ap.add_argument("--pool-factors", default="1,2,4")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ndocs", type=int, default=128)
    ap.add_argument("--keep-dir", default=None,
                    help="keep artifacts here (default: temp dir, removed)")
    ap.add_argument("--out", default="BENCH_persist.json")
    args = ap.parse_args(argv)
    backends = [b for b in args.backends.split(",") if b]
    factors = [int(f) for f in args.pool_factors.split(",") if f]

    cfg = get_smoke_config("colbertv2")
    params = init_colbert(jax.random.PRNGKey(0), cfg)
    spec = replace(DATASET_SPECS[args.dataset], n_docs=args.docs,
                   n_queries=args.queries)
    corpus = SyntheticRetrievalCorpus(spec, vocab_size=cfg.trunk.vocab_size)
    # queries encoded once up front: the cold-path numbers isolate the
    # index artifact, not the query encoder
    searcher = Searcher(params, cfg, index=None)
    qs = searcher.encode_queries(corpus.query_token_batch(cfg.query_maxlen - 2))

    out_root = args.keep_dir or tempfile.mkdtemp(prefix="persist_bench_")
    try:
        results = [bench_cell(params, cfg, corpus, b, f, qs, out_root,
                              args.k, args.ndocs)
                   for b in backends for f in factors]
    finally:
        if args.keep_dir is None:
            shutil.rmtree(out_root, ignore_errors=True)

    out = {"dataset": args.dataset, "n_docs": args.docs,
           "pool_method": "ward", "results": results}
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"\nwrote {args.out}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
