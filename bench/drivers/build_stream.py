"""Streaming index build over an unbounded seeded stream of docs.

The window drives ``repro.Retriever.build`` on the streaming path
(``ShardSpec.shard_max_vectors``): encode, Ward-pool, index and persist
each shard to a scratch directory. The stream yields encode batches
until the window has passed, then stops at the next shard boundary, so
every flushed shard holds the same number of vectors.
``build_docs_per_s`` is the docs in all flushed shards over the time
from the window's start to the build's return (last flush and the root
manifest included).

``correct`` compares a seeded sample of the built docs with the plain
reference (``refs/``), which sees only the seeded tokens and weights:

* ``count_gap``: docs whose stored vector count differs from the
  reference's Ward clustering (``n // factor + 1`` clusters);
* ``ivf_bad``: stored vectors missing from their centroid's IVF list;
* ``doc_cos_gap``: the largest per-token cosine shortfall between the
  doc vectors the window's encoder produced (a seeded sample of its
  batches) and the reference's;
* ``pool_cos_gap``: over seeded docs of a seeded sample of the window's
  pooling calls, the median of each doc's mean shortfall from 1 of the
  cosine between each reference Ward-pooled vector and its closest
  pooled vector the program produced. The median, not the worst doc:
  where two merges are near ties, a bf16 encoder can take the other
  one and move that doc's clusters as far as a lower precision would.

``vec_gap``, the same shortfall against the stored vectors decoded from
the 2-bit codes, is logged but not compared: the codec's own error
moves it as far as a lower-precision encoder does.
"""
from __future__ import annotations

import contextlib
import os
import shutil
import time

import numpy as np

from bench import gen, harness, model
from bench.refs import colbert as ref
from bench.serving import Reservoir, control_dtype, pooled_docs


def _setup(ctx):
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    m = cfg["model"]
    body = int(m["doc_maxlen"]) - 2
    factor = int(cfg["pooling"]["factor"])
    B = int(tr["encode_batch"])
    per_shard = int(tr["batches_per_shard"])
    corpus = gen.corpus_for(cfg, int(m["trunk"]["vocab_size"]), body,
                            ctx.seed, int(tr["max_docs"]), batch=B)
    # every batch holds the same lengths, so the same stored vectors
    # (n // factor + 1 per doc of n emitted tokens); a shard capped at
    # per_shard batches' worth flushes after exactly that many batches
    per_batch = sum((int(L) + 2) // factor + 1 for L in corpus.lengths[:B])
    return cfg, tr, corpus, B, per_shard, per_shard * per_batch


def _spec(cfg, cap: int):
    from repro.core.spec import (IndexSpec, PoolingSpec, RetrieverSpec,
                                 ShardSpec)
    return RetrieverSpec(
        pooling=PoolingSpec(**cfg["pooling"]),
        index=IndexSpec(doc_maxlen=int(cfg["model"]["doc_maxlen"]),
                        **cfg["index"]),
        shard=ShardSpec(shard_max_vectors=cap))


def _stream(corpus, first: int, per_shard: int, until=None, shards=None,
            marks=None):
    """Encode batches from batch ``first``: ``shards`` whole shards, or
    until the clock passes ``until`` at a shard boundary. ``marks``, when
    given, gets the clock as each batch is asked for."""
    b = first
    while True:
        if marks is not None:
            marks.append(time.perf_counter())
        yield corpus.block_tokens(b)
        b += 1
        if (b - first) % per_shard == 0:
            if shards is not None and (b - first) // per_shard >= shards:
                return
            if until is not None and time.perf_counter() >= until:
                return


@contextlib.contextmanager
def _spans():
    """Host spans around the build's layers (traced runs only)."""
    import jax
    from repro.core.index import MultiVectorIndex
    from repro.retrieval.indexer import Indexer
    patched = [(Indexer, "encode_and_pool_counted", "bench.encode_pool"),
               (MultiVectorIndex, "add", "bench.shard_index"),
               (MultiVectorIndex, "save", "bench.shard_save")]
    saved = []
    for cls, name, span in patched:
        fn = getattr(cls, name)
        saved.append((cls, name, fn))

        def wrapped(*a, _fn=fn, _span=span, **kw):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **kw)
        setattr(cls, name, wrapped)
    try:
        yield
    finally:
        for cls, name, fn in saved:
            setattr(cls, name, fn)


def run(ctx):
    from repro.api import Retriever
    import jax
    cfg, tr, corpus, B, per_shard, cap = _setup(ctx)
    pcfg = model.program_config(cfg)
    params = model.make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    ctx.mark("weights made")
    spec = _spec(cfg, cap)
    os.makedirs(ctx.scratch, exist_ok=True)
    # warm-up: one whole shard, from the far end of the stream, so every
    # program of the window (encode, pool, codec training, codec encode)
    # is compiled before it opens
    warm = os.path.join(ctx.scratch, "warm")
    last = int(tr["max_docs"]) // B - per_shard
    Retriever.build(params, pcfg, _stream(corpus, last, per_shard,
                                          shards=1), spec, out_dir=warm)
    ctx.mark("warm-up built")
    shutil.rmtree(warm, ignore_errors=True)
    out = harness.Run()
    out.metrics["setup_s"] = ctx.setup_s()
    ctx.log(f"set-up: {ctx.compiles.n} compiles "
            f"({ctx.compiles.seconds:.1f} s)")
    index_dir = os.path.join(ctx.scratch, "index")
    if ctx.trace:
        harness.start_trace(ctx.options["trace_dir"])
    c0 = ctx.compiles.n
    spans = _spans() if ctx.trace else contextlib.nullcontext()
    captured = Reservoir(int(cfg["check"]["doc_batches"]),
                         np.random.default_rng([ctx.seed, 9]))
    pooled = Reservoir(int(cfg["check"]["doc_batches"]),
                       np.random.default_rng([ctx.seed, 10]))
    marks = []
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN), spans, \
            _capture(captured, pooled):
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        r = Retriever.build(params, pcfg, _stream(
            corpus, 0, per_shard, until=t0 + ctx.seconds, marks=marks),
            spec, out_dir=index_dir)
        t1 = time.perf_counter()
        cpu1 = time.process_time()
    ctx.mark(f"window {t0:.3f}-{t1:.3f} closed")
    if ctx.trace:
        harness.stop_trace()
        out.trace_dir = ctx.options["trace_dir"]
    nc = ctx.compiles.n - c0
    index = r.index
    n_docs = int(index.n_docs)
    out.attempted = n_docs
    out.metrics["build_docs_per_s"] = n_docs / (t1 - t0)
    stats = r.stats
    ctx.log(f"window: {n_docs} docs in {index.n_shards} shards in "
            f"{t1 - t0:.3f} s ({out.metrics['build_docs_per_s']:.1f} "
            f"docs/s), {stats.n_vectors_raw} -> {stats.n_vectors_stored} "
            f"vectors, flush wait {stats.flush_wait_s:.3f} s, compiles "
            f"in window {nc}")
    shard_s = _log_pace(ctx.log, np.array(marks + [t1]), per_shard, stats,
                        cpu1 - cpu0)
    lens = np.minimum(corpus.lengths[:n_docs], corpus.body) + 2
    out.layer = {"docs": n_docs, "window_s": t1 - t0, "doc_lens": lens,
                 "model": cfg["model"], "index": cfg["index"],
                 "stored": int(stats.n_vectors_stored),
                 "docs_per_shard": B * per_shard, "shard_s": shard_s}
    out.memory_peak_bytes = harness.peak_bytes(ctx.devices)
    sample = np.random.default_rng([ctx.seed, 8]).choice(
        n_docs, min(int(cfg["check"]["docs"]), n_docs), replace=False)
    stored = {int(d): stored_doc(index, int(d)) for d in sample}
    del r, index
    out.checks = check(cfg, params, corpus, stored, captured, pooled,
                       cast=control_dtype(ctx), log=ctx.log)
    return out


def _log_pace(log, marks, per_shard: int, stats, cpu_s: float) -> list:
    """Log where the window's time went on the host: batch and shard
    pace, and the slowest batches with the clock at their end, to hold
    against a host stall. Returns each shard's seconds (the last with
    the build's tail)."""
    gaps = np.diff(marks)
    shards = np.add.reduceat(gaps, np.arange(0, len(gaps), per_shard))
    slow = np.argsort(gaps)[::-1][:5]
    log(f"window pace: batch s median {np.median(gaps):.4f}; shard s "
        + " ".join(f"{x:.3f}" for x in shards)
        + "; slowest batches " + " ".join(
            f"#{i}:{gaps[i]:.3f}@{marks[i + 1]:.3f}" for i in slow)
        + f"; flush busy {stats.flush_busy_s:.3f} s; process CPU "
        f"{cpu_s:.1f} s")
    return shards.tolist()


@contextlib.contextmanager
def _capture(encoded: Reservoir, pooled: Reservoir):
    """Keep seeded samples of the window's doc-encoder calls (input
    tokens, output vectors) and pooling calls (input tokens, pooled
    docs) to compare with the reference."""
    from repro.retrieval import indexer
    enc = indexer.encode_docs
    pool = indexer.Indexer.encode_and_pool_counted

    def encoded_docs(params, tokens, cfg):
        out = enc(params, tokens, cfg)
        encoded.offer((tokens, out[0]))
        return out

    def pooled_docs(self, tokens):
        out = pool(self, tokens)
        pooled.offer((tokens, out[0]))
        return out
    indexer.encode_docs = encoded_docs
    indexer.Indexer.encode_and_pool_counted = pooled_docs
    try:
        yield
    finally:
        indexer.encode_docs = enc
        indexer.Indexer.encode_and_pool_counted = pool


def stored_doc(index, d: int):
    """What the build stored for global doc ``d``: (decoded vectors,
    number of its vectors missing from their IVF list), read from the
    flushed shard with a plain numpy decode."""
    s = int(np.searchsorted(np.asarray(index.doc_base), d, "right") - 1)
    p = index.shards[s]._plaid
    local = d - int(index.doc_base[s])
    lo, hi = int(p.doc_offsets[local]), int(p.doc_offsets[local + 1])
    a = np.asarray(p.assignments[lo:hi], np.int64)
    w = np.asarray(p.codes[lo:hi], np.uint32)
    cent = np.asarray(p.codec.centroids, np.float64)
    vals = np.asarray(p.codec.values, np.float64)
    bits, dim = int(p.codec.bits), cent.shape[1]
    per = 32 // bits
    codes = ((w[:, :, None] >> (np.arange(per, dtype=np.uint32) * bits))
             & ((1 << bits) - 1)).reshape(len(w), dim)
    v = cent[a] + vals[np.arange(dim), codes]
    v /= np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-12)
    off, ids = np.asarray(p.ivf.offsets), np.asarray(p.ivf.ids)
    missing = sum(1 for j, c in zip(range(lo, hi), a)
                  if j not in set(ids[off[c]:off[c + 1]].tolist()))
    return v, missing


def shortfall(want, got) -> float:
    """Mean over the reference's vectors ``want`` of one minus the cosine
    to the closest of ``got`` (inf when ``got`` is empty)."""
    if not len(got):
        return np.inf
    g = np.asarray(got, np.float64)
    g = g / np.maximum(np.linalg.norm(g, axis=1, keepdims=True), 1e-12)
    return float(1.0 - (want @ g.T).max(axis=1).mean())


def check(cfg, params, corpus, stored: dict, captured, pooled, cast=None,
          log=print) -> list:
    """The numbers compared for ``correct`` (see module doc). With
    ``cast`` the reference in that lower precision stands in for the
    program (the control); the program's readings are logged too."""
    import json
    m, lim = cfg["model"], cfg["limits"]
    factor = int(cfg["pooling"]["factor"])
    n = int(cfg["check"]["docs"])
    ids = np.array(sorted(stored))
    want = pooled_docs(params, m, corpus, ids, factor)
    got = {"count_gap": float(sum(len(stored[int(d)][0]) != len(want[int(d)])
                                  for d in ids)),
           "ivf_bad": float(sum(stored[int(d)][1] for d in ids)),
           "vec_gap": max(shortfall(want[int(d)], stored[int(d)][0])
                          for d in ids),
           "doc_cos_gap": doc_cos_gap(params, m, captured, n)}
    toks = np.concatenate([np.asarray(t) for t, _ in pooled.items])
    docs = [d for _, ds in pooled.items for d in ds]
    rows = pooled.rng.choice(len(docs), min(n, len(docs)), replace=False)
    toks, docs = toks[rows], [docs[i] for i in rows]
    ref_pooled = pooled_docs(params, m, _Rows(toks), np.arange(len(toks)),
                             factor)
    got["pool_cos_gap"] = float(np.median(
        [shortfall(ref_pooled[i], docs[i]) for i in range(len(docs))]))
    if cast is not None:
        alt = pooled_docs(params, m, corpus, ids, factor, cast=cast)
        got["control_count_gap"] = float(sum(
            len(alt[int(d)]) != len(want[int(d)]) for d in ids))
        got["control_ivf_bad"] = 0.0
        got["control_doc_cos_gap"] = doc_cos_gap(params, m, captured, n,
                                                 cast)
        alt = pooled_docs(params, m, _Rows(toks), np.arange(len(toks)),
                          factor, cast=cast)
        got["control_pool_cos_gap"] = float(np.median(
            [shortfall(ref_pooled[i], alt[i]) for i in range(len(toks))]))
    log("readings: " + json.dumps(got))
    judged = "control_" if cast is not None else ""
    return [harness.Check(k, got[judged + k], float(lim[k]))
            for k in ("count_gap", "ivf_bad", "doc_cos_gap",
                      "pool_cos_gap")]


class _Rows:
    """Token rows that ``pooled_docs`` reads by index, as from a corpus."""

    def __init__(self, toks):
        self.toks = np.asarray(toks)

    def doc_tokens(self, ids):
        return self.toks[np.asarray(ids)]


def doc_cos_gap(params, m: dict, captured, n_docs: int, cast=None):
    """Largest per-token cosine shortfall between the doc vectors the
    window's encoder produced (``n_docs`` seeded rows of a seeded sample
    of its batches) and the float32 reference's."""
    toks = np.concatenate([np.asarray(t) for t, _ in captured.items])
    got = np.concatenate([np.asarray(v) for _, v in captured.items])
    rows = captured.rng.choice(
        len(toks), min(n_docs, len(toks)), replace=False)
    toks, got = toks[rows], got[rows]
    body = toks[:, 2:] if toks.shape[1] == int(m["doc_maxlen"]) else toks
    want = ref.encode_docs(params, m, body)
    if cast is not None:
        alt = ref.encode_docs(params, m, body, cast=cast)
    _, emit = ref.doc_input(body, int(m["doc_maxlen"]))
    worst = 0.0
    for i, w in enumerate(want):
        g = alt[i] if cast is not None else got[i][emit[i]]
        cos = (np.asarray(g, np.float64) * w).sum(-1)
        worst = max(worst, float(1.0 - cos.min()))
    return worst
