"""Retrieval serving driver — closed-loop replay AND open-loop load.

    # closed-loop (fixed microbatches, service-time percentiles):
    python -m repro.launch.serve --dataset scifact --pool-factor 2 \
        --backend plaid --queries 128 --batch-sizes 1,8,32

    # open-loop (Poisson arrivals through the ServingEngine —
    # tail latency under offered load, dynamic batching live):
    python -m repro.launch.serve --dataset scifact --pool-factor 2 \
        --backend plaid --queries 256 --arrival-qps 50,200

``--model`` picks the encoder: ``smoke`` (default, the 2-layer test
trunk) or ``colbertv2`` (the published 12-layer/768-wide ColBERTv2
encoder, 128-d vectors, doc_maxlen 256 — seeded random weights). The
process exits non-zero when any open-loop request fails.

Closed-loop mode replays fixed-size microbatches through the staged
two-stage engine and reports QPS and p50/p99 *service* time per batch
size — exactly ``--queries`` queries are served per row (the final
partial batch is smaller; nothing is silently wrapped and over-counted).

Open-loop mode (``--arrival-qps``) is the deployment-shaped measurement:
single queries arrive with exponential inter-arrival gaps and land on
``launch/engine.py``'s ServingEngine, whose deadline batcher coalesces
them into shape-bucketed microbatches. Reported p50/p99 are end-to-end
request latency (queue wait included) — the number an SLO is written
against — plus the batcher's flush-reason and coalescing stats.

``--index-dir`` makes the index a persistent artifact (core/persist.py):
if the directory already holds a manifest the index is mmap-loaded from
it, otherwise the built index is saved there. In open-loop mode the
engine also WATCHES the directory: re-publishing the artifact (any
``save`` bumps the manifest's monotonic generation) hot-swaps the new
index in with zero dropped queries.

``--shard-max-vectors N`` builds through the STREAMING path instead
(retrieval/indexer.py): token batches are encoded+pooled incrementally
and flushed to capped shards; sharded serving reports per-shard probe
times alongside the percentiles.

The knob flags are DERIVED from the typed spec layer (core/spec.py
``add_spec_args``): --pool-method/--pool-factor come from PoolingSpec,
--max-batch/--max-wait-ms/--k from ServeSpec, --shard-max-vectors from
ShardSpec, and --backend's choices from the backend registry — which is
why ``--backend cascade`` serves the pooled-cascade through the same
engine. Builds and loads go through ``repro.Retriever``.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.api import Retriever
from repro.configs import get_config, get_smoke_config
from repro.core.persist import (MANIFEST_NAME, artifact_bytes,
                                artifact_generation)
from repro.core.sharded import ShardedIndex
from repro.core.spec import (IndexSpec, PoolingSpec, RetrieverSpec,
                             ServeSpec, ShardSpec, add_spec_args,
                             backend_names, spec_from_args)
from repro.data.corpus import DATASET_SPECS, SyntheticRetrievalCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import CompileCounter, ServingEngine, run_open_loop
from repro.models.colbert import init_colbert
from repro.retrieval.searcher import Searcher


MODELS = {"smoke": get_smoke_config, "colbertv2": get_config}


def model_config(name: str):
    """The ColbertConfig behind ``--model``."""
    return MODELS[name]("colbertv2")


def _microbatch_sizes(batch_size: int, n_queries: int):
    sizes = [batch_size] * (n_queries // batch_size)
    if n_queries % batch_size:
        sizes.append(n_queries % batch_size)
    return sizes


def serve_microbatches(searcher: Searcher, q_tokens: np.ndarray,
                       batch_size: int, n_queries: int, k: int = 10):
    """Serve EXACTLY ``n_queries`` in fixed-size microbatches; returns
    (per-batch latencies [s], per-batch served counts).

    The final batch is partial when ``n_queries % batch_size != 0`` —
    earlier versions wrapped around and silently served (and counted)
    extra queries, inflating QPS. Both the full and the remainder batch
    shapes are warmed first so jit compile time never lands in a
    measured batch.
    """
    sizes = _microbatch_sizes(batch_size, n_queries)
    searcher.warmup(sorted(set(sizes)), k=k)
    lat = []
    served = 0
    for bs in sizes:
        # modular gather over the query pool; exactly bs queries served
        idx = (served + np.arange(bs)) % len(q_tokens)
        batch = q_tokens[idx]
        t = time.time()
        searcher.search(batch, k=k)
        lat.append(time.time() - t)
        served += bs
    assert served == n_queries, (served, n_queries)
    return np.array(lat), np.array(sizes)


def _print_probe(index) -> None:
    if isinstance(index, ShardedIndex) and index.last_probe_s:
        per = "  ".join(f"s{i}={t * 1e3:.1f}ms"
                        for i, t in enumerate(index.last_probe_s))
        print(f"      per-shard probe (last batch): {per}")


def closed_loop(searcher, index, q_all, batch_sizes, n_queries, k):
    """Fixed microbatches per batch size; prints and returns one row per
    size (``compiles`` counts XLA compiles inside the timed window —
    the warmup compiles every shape first, so it should be 0)."""
    print(f"{'batch':>5s} {'served':>7s} {'QPS':>8s} "
          f"{'p50(ms)':>8s} {'p99(ms)':>8s} {'compiles':>8s}")
    rows = []
    for bs in batch_sizes:
        searcher.warmup(sorted(set(_microbatch_sizes(bs, n_queries))), k=k)
        with CompileCounter() as cc:
            lat, sizes = serve_microbatches(searcher, q_all, bs, n_queries,
                                            k=k)
        lat_ms = lat * 1e3
        row = {"batch": bs, "served": int(sizes.sum()),
               "qps": float(sizes.sum() / lat.sum()),
               "latency_p50_ms": float(np.percentile(lat_ms, 50)),
               "latency_p99_ms": float(np.percentile(lat_ms, 99)),
               "compiles": cc.count}
        print(f"{bs:5d} {row['served']:7d} {row['qps']:8.1f} "
              f"{row['latency_p50_ms']:8.1f} {row['latency_p99_ms']:8.1f} "
              f"{cc.count:8d}")
        _print_probe(index)
        rows.append(row)
    return rows


def open_loop(searcher, index, q_all, rates, n_queries,
              serve_spec: ServeSpec, index_dir, index_generation):
    """Poisson arrivals through the ServingEngine at each offered rate;
    prints and returns one ``run_open_loop`` row per rate, with the
    in-window compile count (``compiles``) added."""
    print(f"{'offered':>8s} {'achieved':>8s} {'p50(ms)':>8s} "
          f"{'p99(ms)':>8s} {'coalesce':>8s} {'flushes(full/ddl)':>18s} "
          f"{'err':>4s} {'compiles':>8s}")
    rows = []
    for i, rate in enumerate(rates):
        engine = ServingEngine.from_spec(
            searcher, serve_spec.replace(warmup_on_start=(i == 0)),
            index_dir=index_dir, index_generation=index_generation)
        with engine:
            with CompileCounter() as cc:
                row = run_open_loop(engine, q_all, rate, n_queries,
                                    k=serve_spec.k)
        row["compiles"] = cc.count
        snap = engine.stats.snapshot()
        fl = snap["flush_reasons"]
        print(f"{row['arrival_qps']:8.1f} {row['achieved_qps']:8.1f} "
              f"{row['latency_p50_ms']:8.1f} {row['latency_p99_ms']:8.1f} "
              f"{snap['mean_batch_size']:8.1f} "
              f"{fl['full']:8d}/{fl['deadline']:<9d} "
              f"{row['errors']:4d} {cc.count:8d}")
        _print_probe(index)
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="scifact",
                    choices=sorted(DATASET_SPECS))
    ap.add_argument("--model", default="smoke", choices=sorted(MODELS),
                    help="encoder: the 2-layer smoke trunk or the "
                         "full-width ColBERTv2 (random weights)")
    # typed knobs derive their flags from the spec layer (core/spec.py):
    # --pool-method/--pool-factor (PoolingSpec), --max-batch/
    # --max-wait-ms/--k (ServeSpec), --shard-max-vectors (ShardSpec) —
    # no hand-maintained duplicates of the spec defaults/choices here.
    add_spec_args(ap, PoolingSpec, prefix="pool-",
                  defaults={"factor": 2})
    ap.add_argument("--backend", default="plaid", choices=backend_names())
    ap.add_argument("--queries", type=int, default=128,
                    help="total queries served per batch size / rate")
    ap.add_argument("--batch-sizes", default="1,8,32",
                    help="comma-separated closed-loop microbatch sizes")
    ap.add_argument("--arrival-qps", default=None,
                    help="comma-separated offered loads; selects OPEN-LOOP "
                         "mode (Poisson arrivals through the ServingEngine)")
    add_spec_args(ap, ServeSpec,
                  only=("max_batch", "max_wait_ms", "k", "n_replicas"))
    ap.add_argument("--index-dir", default=None,
                    help="artifact directory: load the index from it if "
                         "a manifest exists (skip corpus encode + build), "
                         "otherwise build and save to it; in open-loop "
                         "mode the engine watches it for hot swaps")
    add_spec_args(ap, ShardSpec)
    args = ap.parse_args(argv)
    batch_sizes = [int(b) for b in args.batch_sizes.split(",") if b]
    if not batch_sizes or any(b <= 0 for b in batch_sizes):
        ap.error(f"--batch-sizes must be positive ints, got "
                 f"{args.batch_sizes!r}")
    rates = ([float(r) for r in args.arrival_qps.split(",") if r]
             if args.arrival_qps else [])
    if args.arrival_qps and (not rates or any(r <= 0 for r in rates)):
        ap.error(f"--arrival-qps must be positive, got "
                 f"{args.arrival_qps!r}")

    cfg = model_config(args.model)
    serve_spec = spec_from_args(
        ServeSpec, args,
        only=("max_batch", "max_wait_ms", "k", "n_replicas"))
    try:
        spec = RetrieverSpec(
            pooling=spec_from_args(PoolingSpec, args, prefix="pool_"),
            index=IndexSpec.from_config(cfg, backend=args.backend),
            shard=spec_from_args(ShardSpec, args),
            serve=serve_spec)
    except ValueError as e:             # e.g. cascade + sharded
        ap.error(str(e))
    params = init_colbert(jax.random.PRNGKey(0), cfg)
    corpus = SyntheticRetrievalCorpus(DATASET_SPECS[args.dataset],
                                      vocab_size=cfg.trunk.vocab_size)

    have_artifact = (args.index_dir is not None and os.path.isfile(
        os.path.join(args.index_dir, MANIFEST_NAME)))
    generation = None
    if have_artifact:
        t0 = time.time()
        # generation read BEFORE the load: a racing publish leaves the
        # label stale-low and the engine watcher swaps once, redundantly
        generation = artifact_generation(args.index_dir)
        retriever = Retriever.load(params, cfg, args.index_dir,
                                   mmap=True, serve=serve_spec)
        index = retriever.index
        t_load = time.time() - t0
        kind = (f"{index.n_shards}-shard" if isinstance(index, ShardedIndex)
                else retriever.spec.index.backend)
        print(f"index: loaded {args.index_dir} ({kind}) — "
              f"{index.n_docs} docs, "
              f"{artifact_bytes(args.index_dir) / 2**20:.1f} MiB on disk, "
              f"cold load {t_load * 1e3:.0f}ms (no encoder run)")
    else:
        t0 = time.time()
        toks = corpus.doc_token_batch(cfg.doc_maxlen - 2)
        retriever = Retriever.build(params, cfg, toks, spec,
                                    out_dir=args.index_dir)
        index, stats = retriever.index, retriever.stats
        t_build = time.time() - t0
        shard_note = (f", {stats.n_shards} shards (peak buffer "
                      f"{stats.peak_buffered_vectors} vectors)"
                      if stats.n_shards > 1 else "")
        print(f"index: {stats.n_docs} docs, "
              f"{stats.n_vectors_stored} vectors "
              f"({stats.vector_reduction:.0%} reduction), "
              f"{stats.index_bytes / 2**20:.1f} MiB on disk, "
              f"built in {t_build:.1f}s{shard_note}"
              + (f", saved to {args.index_dir}" if args.index_dir else ""))
        if args.index_dir:                  # our own publish just landed
            generation = artifact_generation(args.index_dir)

    searcher = retriever.searcher
    q_all = corpus.query_token_batch(cfg.query_maxlen - 2)
    if rates:
        rows = open_loop(searcher, index, q_all, rates, args.queries,
                         serve_spec, args.index_dir, generation)
        failed = sum(r["errors"] for r in rows)
        if failed:
            print(f"FAILED: {failed} open-loop request(s) errored")
            return 1
    else:
        closed_loop(searcher, index, q_all, batch_sizes, args.queries,
                    serve_spec.k)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
