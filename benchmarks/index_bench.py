"""Index-build benchmark: monolithic vs streaming, kernel vs reference.

    PYTHONPATH=src python benchmarks/index_bench.py --docs 300 \
        --shard-max-vectors 2048 --out BENCH_index.json

For every pool method x pool factor cell this builds the SAME corpus
several ways and measures

  * ``docs_per_s`` / ``vectors_per_s`` — build throughput (encode +
    pool + index construction, and for streaming also the per-shard
    artifact writes),
  * ``peak_heap_bytes``   — tracemalloc peak of the build phase (numpy
    buffers route through the Python allocator, so this captures the
    host-side high-water mark the streaming path exists to bound; jax
    device buffers are outside tracemalloc, identical for both modes),
  * ``peak_buffered_vectors`` — the streaming builder's own pooled-
    buffer high-water mark (IndexStats),
  * ``transfer_ratio`` — device->host compaction bytes over the padded
    [B, N, d] bytes the pre-kernel path shipped
    (``core.pooling.compaction_transfer_stats``),
  * ``flush_wait_s`` / ``flush_busy_s`` — the pipelined streaming
    build's encode-stall and shard-I/O wall (IndexStats).

Modes per cell: ``monolithic`` and ``streaming-sharded`` are the
serial builds on the REFERENCE ward path (comparable against pre-kernel
history rows); ward cells with factor > 1 additionally run
``monolithic-kernel`` (Pallas ward_pool path) and
``streaming-pipelined`` (kernel path + background flush thread).

ASSERTED acceptance bounds:
  * streaming with a cap below the corpus -> >= 2 shards, pooled buffer
    within ``cap + max_batch_vectors`` (docs are atomic; flush check
    runs once per encode batch),
  * kernel cells: search results of the kernel-built monolithic index
    bitwise == the reference-built one, and the pipelined+kernel
    streaming ARTIFACT content-identical (generation tokens
    canonicalized out) to the serial+reference one — assignments,
    shard layout, doc ids, and payload bytes all pinned,
  * kernel cells: compaction transfer <= 1/factor + eps of padded
    bytes,
  * with ``--assert-pipeline``: pipelined streaming no slower than
    0.95x serial, and encode stalls behind shard I/O under 5% of the
    build (the CI gate).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import resource
import shutil
import tempfile
import time
import tracemalloc
from dataclasses import replace

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core.pooling import compaction_transfer_stats
from repro.data.corpus import DATASET_SPECS, SyntheticRetrievalCorpus
from repro.models.colbert import init_colbert
from repro.retrieval.indexer import Indexer

_TOKEN = re.compile(r"\.[0-9a-f]{8}\.npy")


def _measured(fn):
    """(result, wall seconds, tracemalloc peak bytes) for one build."""
    tracemalloc.start()
    tracemalloc.reset_peak()
    t0 = time.time()
    out = fn()
    dt = time.time() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    return out, dt, peak


def _canonical_artifact(root: str) -> dict:
    """Artifact content keyed by token-stripped relpath (filenames embed
    a random generation token; content must not differ)."""
    out = {}
    for dirpath, _, files in os.walk(root):
        for name in sorted(files):
            if name == "stats.json":    # build timings, not content
                continue
            path = os.path.join(dirpath, name)
            rel = _TOKEN.sub(".npy", os.path.relpath(path, root))
            with open(path, "rb") as fh:
                blob = fh.read()
            out[rel] = (_TOKEN.sub(".npy", blob.decode())
                        if name.endswith(".json") else blob)
    return out


def _assert_same_artifact(dir_a: str, dir_b: str, what: str) -> None:
    ca, cb = _canonical_artifact(dir_a), _canonical_artifact(dir_b)
    assert sorted(ca) == sorted(cb), (
        f"{what}: artifact layout drift {sorted(set(ca) ^ set(cb))}")
    for rel in ca:
        assert ca[rel] == cb[rel], f"{what}: content drift in {rel}"


def bench_cell(params, cfg, toks, method: str, factor: int, backend: str,
               cap: int, out_root: str, encode_batch: int,
               assert_pipeline: bool = False):
    def make_indexer(ward_kernel: str = "ref"):
        from repro.core.spec import IndexSpec, PoolingSpec
        return Indexer(
            params, cfg, encode_batch=encode_batch,
            index_spec=IndexSpec.from_config(cfg, backend=backend,
                                             ndocs=4096),
            pooling_spec=PoolingSpec(method=method,
                                     factor=max(factor, 1),
                                     ward_kernel=ward_kernel))

    kernel_cell = method == "ward" and factor > 1

    # warm the encoder + pooling traces (both impls for kernel cells) so
    # jit compile lands in no measurement
    make_indexer("ref").encode_and_pool(toks[:encode_batch])
    if kernel_cell:
        make_indexer("kernel").encode_and_pool(toks[:encode_batch])

    (mono_ix, mono_stats), mono_s, mono_peak = _measured(
        lambda: make_indexer("ref").build(toks))

    rows = []

    def row(mode, stats, secs, peak, ward_kernel="ref", extra=None):
        r = {
            "method": method, "factor": factor, "backend": backend,
            "mode": mode, "ward_kernel": ward_kernel,
            "n_docs": stats.n_docs, "n_shards": stats.n_shards,
            "n_vectors_stored": stats.n_vectors_stored,
            "docs_per_s": stats.n_docs / max(secs, 1e-9),
            "vectors_per_s": stats.n_vectors_stored / max(secs, 1e-9),
            "build_s": secs,
            "peak_heap_bytes": int(peak),
            "peak_buffered_vectors": stats.peak_buffered_vectors,
            "index_bytes": stats.index_bytes,
            "flush_wait_s": stats.flush_wait_s,
            "flush_busy_s": stats.flush_busy_s,
        }
        r.update(extra or {})
        rows.append(r)
        return r

    row("monolithic", mono_stats, mono_s, mono_peak)

    kern_s = None
    if kernel_cell:
        compaction_transfer_stats(reset=True)
        (kern_ix, kern_stats), kern_s, kern_peak = _measured(
            lambda: make_indexer("kernel").build(toks))
        ts = compaction_transfer_stats(reset=True)
        ratio = ts["compact_bytes"] / max(ts["padded_bytes"], 1)
        # ---- gate: compaction ships <= 1/factor + eps of padded bytes
        eps = 2.0 / cfg.doc_maxlen + 0.02
        assert ratio <= 1.0 / factor + eps, (
            f"compaction transfer ratio {ratio:.3f} above "
            f"1/{factor} + {eps:.3f}")
        # ---- gate: kernel-built index searches bitwise like the ref's
        rng = np.random.default_rng(0)
        qs = rng.normal(size=(8, 8, cfg.proj_dim)).astype(np.float32)
        for ra, rb in zip(mono_ix.search_batch(qs, k=10),
                          kern_ix.search_batch(qs, k=10)):
            assert (np.asarray(ra) == np.asarray(rb)).all(), (
                "kernel-vs-reference search parity mismatch")
        assert kern_stats.n_vectors_stored == mono_stats.n_vectors_stored
        row("monolithic-kernel", kern_stats, kern_s, kern_peak,
            ward_kernel="kernel", extra={"transfer_ratio": ratio})

    # cap is a ceiling: higher pool factors shrink the corpus, so keep
    # the cap below ~1/3 of the stored vectors or the cell can't shard
    cap = min(cap, max(mono_stats.n_vectors_stored // 3, 1))
    art = os.path.join(out_root, f"{method}_f{factor}")
    (sharded, st), stream_s, stream_peak = _measured(
        lambda: make_indexer("ref").build_streaming(
            toks, shard_max_vectors=cap, out_dir=art, pipeline=False))

    # ---- acceptance bound: bounded host buffer, real sharding ----
    assert st.n_shards >= 2, (
        f"cap {cap} did not shard a {st.n_vectors_stored}-vector corpus")
    bound = cap + st.max_batch_vectors
    assert st.peak_buffered_vectors <= bound, (
        f"streaming buffer {st.peak_buffered_vectors} exceeded "
        f"cap+batch bound {bound}")
    assert st.n_vectors_stored == mono_stats.n_vectors_stored
    row("streaming-sharded", st, stream_s, stream_peak)

    if kernel_cell:
        art_pipe = os.path.join(out_root, f"{method}_f{factor}_pipe")
        (_, stp), pipe_s, pipe_peak = _measured(
            lambda: make_indexer("kernel").build_streaming(
                toks, shard_max_vectors=cap, out_dir=art_pipe,
                pipeline=True))
        # ---- gate: pipelined+kernel artifact == serial+reference ----
        _assert_same_artifact(art, art_pipe,
                              f"{method} f={factor} pipelined streaming")
        assert stp.peak_buffered_vectors == st.peak_buffered_vectors
        row("streaming-pipelined", stp, pipe_s, pipe_peak,
            ward_kernel="kernel")
        if assert_pipeline:
            # (b) pipelined must not lose to serial (5% noise floor) and
            # encode must not sit idle behind shard I/O
            assert pipe_s <= stream_s / 0.95, (
                f"pipelined streaming {pipe_s:.3f}s slower than serial "
                f"{stream_s:.3f}s")
            assert stp.flush_wait_s <= 0.05 * pipe_s, (
                f"encode stalled {stp.flush_wait_s:.3f}s behind shard "
                f"I/O in a {pipe_s:.3f}s build")

    for r in rows:
        print(f"{method:10s} f={factor} {r['mode']:19s} "
              f"{r['docs_per_s']:7.1f} docs/s {r['vectors_per_s']:9.0f} "
              f"vec/s  peak-heap {r['peak_heap_bytes'] / 2**20:7.1f} MiB"
              + (f"  shards={r['n_shards']} "
                 f"buf<={r['peak_buffered_vectors']}"
                 if r["mode"].startswith("streaming") else ""))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="scifact")
    ap.add_argument("--docs", type=int, default=300)
    ap.add_argument("--methods", default="ward,sequential")
    ap.add_argument("--pool-factors", default="1,2,4")
    ap.add_argument("--backend", default="flat",
                    help="index backend under the build (flat isolates "
                         "encode+pool+store cost; plaid adds codec train)")
    ap.add_argument("--shard-max-vectors", type=int, default=2048)
    ap.add_argument("--encode-batch", type=int, default=32)
    ap.add_argument("--assert-pipeline", action="store_true",
                    help="fail if the pipelined streaming build is "
                         "slower than serial or encode stalls on I/O")
    ap.add_argument("--keep-dir", default=None)
    ap.add_argument("--out", default="BENCH_index.json")
    args = ap.parse_args(argv)
    methods = [m for m in args.methods.split(",") if m]
    factors = [int(f) for f in args.pool_factors.split(",") if f]

    cfg = get_smoke_config("colbertv2")
    params = init_colbert(jax.random.PRNGKey(0), cfg)
    spec = replace(DATASET_SPECS[args.dataset], n_docs=args.docs)
    corpus = SyntheticRetrievalCorpus(spec, vocab_size=cfg.trunk.vocab_size)
    toks = corpus.doc_token_batch(cfg.doc_maxlen - 2)

    out_root = args.keep_dir or tempfile.mkdtemp(prefix="index_bench_")
    try:
        results = []
        for m in methods:
            for f in factors:
                results += bench_cell(params, cfg, toks, m, f,
                                      args.backend, args.shard_max_vectors,
                                      out_root, args.encode_batch,
                                      assert_pipeline=args.assert_pipeline)
    finally:
        if args.keep_dir is None:
            shutil.rmtree(out_root, ignore_errors=True)

    out = {"dataset": args.dataset, "n_docs": args.docs,
           "backend": args.backend,
           "shard_max_vectors": args.shard_max_vectors,
           "ru_maxrss_kb": resource.getrusage(resource.RUSAGE_SELF)
                                   .ru_maxrss,
           "results": results}
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"\nwrote {args.out}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
