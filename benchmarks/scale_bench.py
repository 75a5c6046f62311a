"""Scale-out serving benchmark: QPS vs replica count on a large corpus.

    PYTHONPATH=src python benchmarks/scale_bench.py --docs 1000000

Builds a synthetic unit-vector corpus straight into a ShardedIndex
(identity encoder — the corpus IS the pooled vectors, so a million-doc
build costs index construction, not a transformer forward), then for
each replica count serves the SAME index through the engine's replica
router (launch/engine.py ``n_replicas``) and records:

  * saturation QPS — a closed burst of single-query requests through
    the dynamic batcher, wall-clock timed: the capacity number the
    replica-scaling headline (``speedup_vs_1``) is computed from;
  * an open-loop Poisson run offered at ``--load-frac`` of that
    measured capacity: achieved QPS + end-to-end p50/p99 — the
    "bounded p99 at high utilization" evidence, per replica count;
  * a bitwise parity audit: every open-loop result AND every replica
    lane's direct ``search_batch_on`` checked against the wrapped
    index's ``search_batch`` (ids + scores).

Honesty fields: ``host_cores`` and ``n_devices`` are recorded because
replica scaling is bounded by physical parallelism — on a 1-core box
every lane shares one execution stream and speedup_vs_1 ~ 1.0 by
construction. The CI ``scale-smoke`` job runs this with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (+ single-thread
eigen) on a multi-core runner and gates ``--min-speedup`` there.

``--assert-parity`` exits non-zero on any mismatch or failed query;
``--min-speedup S`` additionally requires QPS(max replicas) >=
S x QPS(1).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.sharded import ShardedIndex
from repro.core.spec import ServeSpec, add_spec_args
from repro.launch.engine import ServingEngine, run_open_loop


class VectorSearcher:
    """Identity-encoder searcher: 'query tokens' are already [n, Lq, dim]
    unit vectors, so the bench measures the serving/index layers, not a
    transformer forward."""

    def __init__(self, index):
        self.index = index

    def encode_queries(self, q):
        return np.asarray(q, np.float32)

    def warmup(self, batch_sizes, k=10):
        if isinstance(batch_sizes, (int, np.integer)):
            batch_sizes = [batch_sizes]
        lq, dim = self._qshape
        for bs in sorted(set(batch_sizes)):
            self.index.search_batch(
                np.zeros((bs, lq, dim), np.float32), k=k)


def unit(rng, shape):
    v = rng.normal(size=shape).astype(np.float32)
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def build_corpus(args):
    """Chunked adds: peak host memory is one chunk of docs plus the
    index itself, never the whole corpus as a python list."""
    rng = np.random.default_rng(args.seed)
    kw = dict(doc_maxlen=args.doc_len,
              n_centroids=args.n_centroids, nprobe=args.nprobe,
              ndocs=args.ndocs)
    index = ShardedIndex(dim=args.dim, backend=args.backend,
                         shard_max_vectors=args.shard_max_vectors,
                         **(kw if args.backend == "plaid"
                            else dict(doc_maxlen=args.doc_len)))
    t0 = time.time()
    chunk = args.build_chunk
    added = 0
    while added < args.docs:
        n = min(chunk, args.docs - added)
        # fixed doc length: the corpus is synthetic; ragged lengths only
        # slow construction without changing what scaling is measured
        vecs = unit(rng, (n, args.doc_len, args.dim))
        index.add(list(vecs))
        added += n
        if added % (chunk * 8) == 0 or added == args.docs:
            print(f"  built {added}/{args.docs} docs "
                  f"({index.n_shards} shards, {time.time() - t0:.0f}s)",
                  flush=True)
    return index, time.time() - t0


def lane_parity(index, wrapped, qs, k):
    """Every replica lane vs the wrapped index's own search_batch."""
    S0, I0 = index.search_batch(qs, k=k)
    bad = 0
    n_lanes = getattr(wrapped, "n_replicas", 1)
    for r in range(n_lanes):
        S, I = (wrapped.search_batch_on(r, qs, k=k)
                if hasattr(wrapped, "search_batch_on")
                else wrapped.search_batch(qs, k=k))
        if not (np.array_equal(np.asarray(S), np.asarray(S0))
                and np.array_equal(np.asarray(I), np.asarray(I0))):
            bad += 1
    return bad, (S0, I0)


def saturation_qps(engine, qs, n_queries, k):
    """Closed burst: submit everything, wall-clock the drain."""
    t0 = time.perf_counter()
    futs = [engine.submit(qs[i % len(qs)][None], k=k)
            for i in range(n_queries)]
    errors = 0
    for f in futs:
        try:
            f.result(timeout=300.0)
        except Exception:               # noqa: BLE001
            errors += 1
    wall = time.perf_counter() - t0
    return (n_queries - errors) / wall if wall > 0 else 0.0, errors


def scale_cell(index, qs, n_replicas, args, refs):
    searcher = VectorSearcher(index)
    searcher._qshape = qs.shape[1:]
    engine = ServingEngine(searcher, max_batch=args.max_batch,
                           max_wait_ms=args.max_wait_ms, k=args.k,
                           warmup_on_start=False, n_replicas=n_replicas)
    # warm every lane at every bucket shape BEFORE timing (the engine's
    # default warmup path needs an encoder config; the identity searcher
    # warms through the placed index directly)
    served = engine._handle.index
    for b in engine.buckets:
        warm = getattr(served, "warm_shapes", None)
        if warm is not None:
            warm(np.broadcast_to(qs[:1], (b,) + qs.shape[1:]), k=args.k)
        else:
            served.search_batch(
                np.broadcast_to(qs[:1], (b,) + qs.shape[1:]), k=args.k)
    mismatched_lanes, (S_ref, I_ref) = lane_parity(index, served, qs,
                                                   args.k)
    with engine:
        qps_sat, sat_errors = saturation_qps(engine, qs,
                                             args.queries, args.k)
        rate = max(args.load_frac * qps_sat, 1.0)
        ol = run_open_loop(engine, qs, rate, args.queries, k=args.k,
                           seed=args.seed, collect_results=True)
        snap = engine.stats.snapshot()
    results = ol.pop("results")
    ol_mismatches = 0
    for i, res in enumerate(results):
        if res is None:
            continue
        S, I = res
        j = i % len(qs)
        if not (np.array_equal(S[0], S_ref[j])
                and np.array_equal(I[0], I_ref[j])):
            ol_mismatches += 1
    row = {
        "n_replicas": n_replicas,
        "qps_saturated": qps_sat,
        "saturation_errors": sat_errors,
        "open_loop": ol,
        "lane_parity_mismatches": mismatched_lanes,
        "open_loop_parity_mismatches": ol_mismatches,
        "replica_batches": snap["replica_batches"],
        "mean_batch_size": snap["mean_batch_size"],
    }
    refs[n_replicas] = qps_sat
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--docs", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--doc-len", type=int, default=4,
                    help="pooled vectors per doc (the paper's pooled "
                         "regime: a few vectors, not hundreds)")
    ap.add_argument("--backend", default="plaid",
                    choices=["flat", "plaid"],
                    help="plaid bounds per-query cost by the candidate "
                         "budget at any corpus size; flat is the "
                         "shard_map SPMD path (small corpora)")
    ap.add_argument("--shard-max-vectors", type=int, default=0,
                    help="0 = auto: ~8 shards over the corpus")
    ap.add_argument("--n-centroids", type=int, default=256)
    ap.add_argument("--nprobe", type=int, default=4)
    ap.add_argument("--ndocs", type=int, default=512,
                    help="plaid candidate budget (caps stage-2 cost)")
    ap.add_argument("--build-chunk", type=int, default=20_000)
    ap.add_argument("--replicas", default="1,2,4")
    ap.add_argument("--queries", type=int, default=256,
                    help="requests per saturation burst / open-loop run")
    ap.add_argument("--query-pool", type=int, default=64)
    ap.add_argument("--lq", type=int, default=8)
    ap.add_argument("--load-frac", type=float, default=0.7,
                    help="open-loop offered load as a fraction of the "
                         "cell's measured saturation QPS")
    ap.add_argument("--seed", type=int, default=0)
    add_spec_args(ap, ServeSpec, only=("max_batch", "max_wait_ms", "k"))
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="gate: QPS(max replicas) >= S x QPS(1)")
    ap.add_argument("--assert-parity", action="store_true")
    ap.add_argument("--out", default="BENCH_scale.json")
    args = ap.parse_args(argv)
    replicas = sorted({int(r) for r in args.replicas.split(",") if r})

    if args.shard_max_vectors == 0:
        args.shard_max_vectors = max(1, args.docs * args.doc_len // 8)

    import jax
    print(f"building {args.docs} docs x {args.doc_len} vectors "
          f"({args.backend})...", flush=True)
    index, build_s = build_corpus(args)
    rng = np.random.default_rng(args.seed + 1)
    qs = unit(rng, (args.query_pool, args.lq, args.dim))

    cells, refs = [], {}
    for n in replicas:
        print(f"replica cell n={n}...", flush=True)
        cells.append(scale_cell(index, qs, n, args, refs))
        c = cells[-1]
        print(f"  qps_sat={c['qps_saturated']:.1f} "
              f"p99={c['open_loop']['latency_p99_ms']:.1f}ms "
              f"lane_mismatch={c['lane_parity_mismatches']} "
              f"ol_mismatch={c['open_loop_parity_mismatches']}",
              flush=True)

    top = max(replicas)
    speedup = (refs[top] / refs[1]
               if 1 in refs and top != 1 and refs[1] > 0 else 1.0)
    out = {
        "host_cores": os.cpu_count(),
        "n_devices": len(jax.devices()),
        "docs": args.docs,
        "vectors": index.n_vectors(),
        "n_shards": index.n_shards,
        "backend": args.backend,
        "dim": args.dim,
        "build_s": build_s,
        "k": args.k,
        "max_batch": args.max_batch,
        "load_frac": args.load_frac,
        "cells": cells,
        "speedup_vs_1": {"n_replicas": top, "qps_ratio": speedup},
    }
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"wrote {args.out}; speedup({top} vs 1) = {speedup:.2f}x "
          f"on {os.cpu_count()} cores / {len(jax.devices())} devices")

    failures = []
    mism = sum(c["lane_parity_mismatches"]
               + c["open_loop_parity_mismatches"] for c in cells)
    errs = sum(c["saturation_errors"] + c["open_loop"]["errors"]
               for c in cells)
    if args.assert_parity and (mism or errs):
        failures.append(f"parity mismatches={mism} errors={errs}")
    if args.min_speedup is not None and speedup < args.min_speedup:
        failures.append(f"speedup {speedup:.2f}x < required "
                        f"{args.min_speedup:.2f}x")
    if failures:
        print("SCALE BENCH FAILED: " + "; ".join(failures))
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
