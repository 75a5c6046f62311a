"""Streaming index build of long documents, judged against the reference
that the configuration names (its ``reference`` file) with the weights
of its own model maker (``bench/modernbert.py``).

The stream, window and numbers compared are ``build_stream``'s (see its
module doc), with the traffic's encode batch passed to
``Retriever.build``: ``count_gap``, ``ivf_bad``, ``doc_cos_gap`` and
``pool_cos_gap``, computed with this configuration's reference, which
runs in calls of a few docs so that its dense [S, S] scores fit the
chip beside the program.

A program that cannot build the configuration's model fails at once,
before any corpus or weight is made.
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

from bench import harness, model, modernbert
from bench.drivers import build_stream as bs
from bench.serving import Reservoir, control_dtype


def run(ctx):
    from repro.api import Retriever
    import jax
    cfg = ctx.cell.config
    pcfg = model.program_config(cfg)  # raises on a program without the model
    ref = harness.load_module(ctx.cell.path(ctx.cell.bench["paths"][0],
                                            cfg["reference"]))
    _, tr, corpus, B, per_shard, cap = bs._setup(ctx)
    params = modernbert.make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    ctx.mark("weights made")
    spec = bs._spec(cfg, cap)
    os.makedirs(ctx.scratch, exist_ok=True)
    # warm-up: one whole shard from the far end of the stream compiles
    # every program of the window
    warm = os.path.join(ctx.scratch, "warm")
    last = int(tr["max_docs"]) // B - per_shard
    Retriever.build(params, pcfg, bs._stream(corpus, last, per_shard,
                                             shards=1),
                    spec, out_dir=warm, encode_batch=B)
    ctx.mark("warm-up built")
    shutil.rmtree(warm, ignore_errors=True)
    out = harness.Run()
    out.metrics["setup_s"] = ctx.setup_s()
    ctx.log(f"set-up: {ctx.compiles.n} compiles "
            f"({ctx.compiles.seconds:.1f} s)")
    if ctx.trace:
        harness.start_trace(ctx.options["trace_dir"])
    c0 = ctx.compiles.n
    captured = Reservoir(int(cfg["check"]["doc_batches"]),
                         np.random.default_rng([ctx.seed, 9]))
    pooled = Reservoir(int(cfg["check"]["doc_batches"]),
                       np.random.default_rng([ctx.seed, 10]))
    marks = []
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN), \
            bs._capture(captured, pooled):
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        r = Retriever.build(params, pcfg, bs._stream(
            corpus, 0, per_shard, until=t0 + ctx.seconds, marks=marks),
            spec, out_dir=os.path.join(ctx.scratch, "index"),
            encode_batch=B)
        t1 = time.perf_counter()
        cpu1 = time.process_time()
    ctx.mark(f"window {t0:.3f}-{t1:.3f} closed")
    if ctx.trace:
        harness.stop_trace()
        out.trace_dir = ctx.options["trace_dir"]
    index, stats = r.index, r.stats
    n_docs = int(index.n_docs)
    out.attempted = n_docs
    out.metrics["build_docs_per_s"] = n_docs / (t1 - t0)
    ctx.log(f"window: {n_docs} docs in {index.n_shards} shards in "
            f"{t1 - t0:.3f} s ({out.metrics['build_docs_per_s']:.2f} "
            f"docs/s), {stats.n_vectors_raw} -> {stats.n_vectors_stored} "
            f"vectors, flush wait {stats.flush_wait_s:.3f} s, compiles "
            f"in window {ctx.compiles.n - c0}")
    shard_s = bs._log_pace(ctx.log, np.array(marks + [t1]), per_shard,
                           stats, cpu1 - cpu0)
    out.layer = {"docs": n_docs, "window_s": t1 - t0,
                 "doc_lens": np.minimum(corpus.lengths[:n_docs],
                                        corpus.body) + 2,
                 "model": cfg["model"], "index": cfg["index"],
                 "stored": int(stats.n_vectors_stored),
                 "docs_per_shard": B * per_shard, "shard_s": shard_s}
    out.memory_peak_bytes = harness.peak_bytes(ctx.devices)
    sample = np.random.default_rng([ctx.seed, 8]).choice(
        n_docs, min(int(cfg["check"]["docs"]), n_docs), replace=False)
    stored = {int(d): bs.stored_doc(index, int(d)) for d in sample}
    del r, index
    out.checks = check(ref, cfg, params, corpus, stored, captured, pooled,
                       cast=control_dtype(ctx), log=ctx.log)
    return out


def pooled_docs(ref, params, m: dict, corpus, ids, factor: int,
                cast=None) -> dict:
    """{doc id: reference Ward-pooled vectors} for docs ``ids``."""
    vecs = ref.encode_docs(params, m, corpus.doc_tokens(ids), cast=cast)
    return {int(d): ref.ward(v, factor) for d, v in zip(ids, vecs)}


def doc_cos_gap(ref, params, m: dict, captured, n_docs: int, cast=None):
    """Largest per-token cosine shortfall between the doc vectors the
    window's encoder produced (``n_docs`` seeded rows of a seeded sample
    of its batches) and the float32 reference's; with ``cast``, the
    lower-precision reference's in the program's place."""
    toks = np.concatenate([np.asarray(t) for t, _ in captured.items])
    got = np.concatenate([np.asarray(v) for _, v in captured.items])
    rows = captured.rng.choice(len(toks), min(n_docs, len(toks)),
                               replace=False)
    toks, got = toks[rows], got[rows]
    want = ref.encode_docs(params, m, toks)
    if cast is not None:
        got = ref.encode_docs(params, m, toks, cast=cast)
    else:
        _, emit = ref.doc_input(toks, m)
        got = [g[e] for g, e in zip(got, emit)]
    return max(float(1.0 - (np.asarray(g, np.float64) * w).sum(-1).min())
               for g, w in zip(got, want))


def check(ref, cfg, params, corpus, stored: dict, captured, pooled,
          cast=None, log=print) -> list:
    """The numbers compared for ``correct`` (``build_stream.check``'s,
    with this configuration's reference)."""
    import json
    m, lim = cfg["model"], cfg["limits"]
    factor = int(cfg["pooling"]["factor"])
    n = int(cfg["check"]["docs"])
    ids = np.array(sorted(stored))
    want = pooled_docs(ref, params, m, corpus, ids, factor)
    got = {"count_gap": float(sum(len(stored[int(d)][0]) != len(want[int(d)])
                                  for d in ids)),
           "ivf_bad": float(sum(stored[int(d)][1] for d in ids)),
           "vec_gap": max(bs.shortfall(want[int(d)], stored[int(d)][0])
                          for d in ids),
           "doc_cos_gap": doc_cos_gap(ref, params, m, captured, n)}
    toks = np.concatenate([np.asarray(t) for t, _ in pooled.items])
    docs = [d for _, ds in pooled.items for d in ds]
    rows = pooled.rng.choice(len(docs), min(n, len(docs)), replace=False)
    toks, docs = bs._Rows(toks[rows]), [docs[i] for i in rows]
    every = np.arange(len(docs))
    ref_pooled = pooled_docs(ref, params, m, toks, every, factor)
    got["pool_cos_gap"] = float(np.median(
        [bs.shortfall(ref_pooled[i], docs[i]) for i in every]))
    if cast is not None:
        alt = pooled_docs(ref, params, m, corpus, ids, factor, cast=cast)
        got["control_count_gap"] = float(sum(
            len(alt[int(d)]) != len(want[int(d)]) for d in ids))
        got["control_ivf_bad"] = 0.0
        got["control_doc_cos_gap"] = doc_cos_gap(ref, params, m, captured,
                                                 n, cast)
        alt = pooled_docs(ref, params, m, toks, every, factor, cast=cast)
        got["control_pool_cos_gap"] = float(np.median(
            [bs.shortfall(ref_pooled[i], alt[i]) for i in every]))
    log("readings: " + json.dumps(got))
    judged = "control_" if cast is not None else ""
    return [harness.Check(k, got[judged + k], float(lim[k]))
            for k in ("count_gap", "ivf_bad", "doc_cos_gap",
                      "pool_cos_gap")]
