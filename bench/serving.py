"""Set-up and the check of ``correct`` shared by the served cells.

Set-up builds the configuration's index through the program's own
entry points (``repro.Retriever.build``, PLAID backend, Ward pooling)
from a seeded corpus and seeded weights, starts a ``ServingEngine``
(which warms its shape buckets), and reads the device memory the index
holds per doc. The window then drives ``ServingEngine.submit``.

``correct`` compares a seeded sample of the requests served in the
window with the plain reference (``refs/``), which sees only the
seeded tokens and weights:

* ``query_cos_gap``: the largest per-token cosine shortfall between
  the query vectors the window's encoder produced (a seeded sample of
  its calls) and the reference's.
* ``recall_gap``: one minus the mean recall@k of the returned ids
  against the reference's exact top-k within a judged pool: the
  request's returned docs, its query's source doc and a seeded sample
  of ``pool_docs`` docs, all scored by float64 MaxSim over the
  reference's own Ward-pooled float32 vectors. A correct search returns
  the corpus's best docs, which nothing in the pool outranks; answers
  swapped between requests, shifted ids or missed candidates are
  outranked by the source doc and the pool's docs of the same topic.
* ``bad_ids``: returned ids out of range, or repeated within a row (a
  -1 pad, where a query's candidate set holds fewer than k docs, is an
  answer the index may give).

The control (``--control <dtype>``) puts the reference computed in that
lower precision in the program's place: its query vectors, and its
top-k within the same pools. Its run logs the program's readings too.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from bench import gen, harness, model
from bench.refs import colbert as ref


class Reservoir:
    """A seeded uniform sample of ``n`` calls of a stage in the timed
    path, kept as (input tokens, output) pairs: wraps the stage's
    method so the window's own outputs can be compared afterwards."""

    def __init__(self, n: int, rng):
        self.n, self.rng, self.seen, self.items = n, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.n:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.n:
                self.items[j] = item

    def wrap(self, obj, name: str) -> None:
        fn = getattr(obj, name)

        def recorded(tokens, *a, **kw):
            out = fn(tokens, *a, **kw)
            self.offer((tokens, out))
            return out
        setattr(obj, name, recorded)


ENCODE_BATCH = 64          # the program's default encode batch, pinned


class Served:
    """The cell's live system and what set-up measured."""

    def __init__(self, ctx):
        from repro.api import Retriever
        from repro.core.spec import (IndexSpec, PoolingSpec, RetrieverSpec,
                                     ServeSpec)
        from repro.launch.engine import ServingEngine
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.cfg, self.traffic, self.ctx = cfg, tr, ctx
        self.pcfg = model.program_config(cfg)
        m = cfg["model"]
        dev = ctx.devices[0]
        n_docs = int(cfg["deployment"]["n_docs"])
        self.corpus = gen.corpus_for(cfg, int(m["trunk"]["vocab_size"]),
                                     int(m["doc_maxlen"]) - 2, ctx.seed,
                                     n_docs, batch=ENCODE_BATCH)
        doc_tokens = self.corpus.all_tokens()
        ql = tr["query_len"]
        self.q_tokens, self.q_src = self.corpus.queries(
            int(tr["query_pool"]), int(ql[0]), int(ql[1]),
            int(m["query_maxlen"]) - 2)
        self.params = model.make_params(cfg, ctx.seed)
        import jax
        jax.block_until_ready(self.params)
        mem_weights = harness.bytes_in_use(dev)
        spec = RetrieverSpec(
            pooling=PoolingSpec(**cfg["pooling"]),
            index=IndexSpec(doc_maxlen=int(m["doc_maxlen"]),
                            **cfg["index"]))
        self.retriever = Retriever.build(self.params, self.pcfg, doc_tokens,
                                         spec, encode_batch=ENCODE_BATCH)
        del doc_tokens
        serve = ServeSpec(max_batch=int(tr["max_batch"]),
                          max_wait_ms=float(tr["max_wait_ms"]),
                          k=int(tr["k"]))
        self.engine = ServingEngine.from_spec(self.retriever.searcher, serve)
        self.engine.start()              # warms every shape bucket
        self.captured = Reservoir(int(cfg["check"]["query_batches"]),
                                  np.random.default_rng([ctx.seed, 9]))
        self.captured.wrap(self.engine.searcher, "encode_queries")
        self.k = serve.k
        self.n_docs = n_docs
        self.stored_vectors = int(self.retriever.index.n_vectors())
        self.hbm_bytes_per_doc = (harness.bytes_in_use(dev)
                                  - mem_weights) / n_docs
        ctx.log(f"set-up: {n_docs} docs, "
                f"{self.stored_vectors} stored vectors, "
                f"{self.hbm_bytes_per_doc:.1f} device bytes per doc, "
                f"{ctx.compiles.n} compiles ({ctx.compiles.seconds:.1f} s)")
        ctx.log("device bytes by live array: " + live_arrays(dev))

    def annotate(self):
        """Wrap the engine's two stages in host spans (traced runs):
        ``bench.encode`` and ``bench.search`` label the device's idle
        gaps in the breakdown."""
        import jax
        s, ix = self.engine.searcher, self.engine._handle.index
        enc, search = s.encode_queries, ix.search_batch

        def encode_queries(*a, **kw):
            with jax.profiler.TraceAnnotation("bench.encode"):
                return enc(*a, **kw)

        def search_batch(*a, **kw):
            with jax.profiler.TraceAnnotation("bench.search"):
                return search(*a, **kw)
        s.encode_queries = encode_queries
        ix.search_batch = search_batch

    def layer_inputs(self, batches: int, served: int, seconds: float
                     ) -> dict:
        """What the per-layer readers read about a window of
        ``batches`` engine batches and ``served`` requests."""
        st = self.engine.stats
        return {"served": served, "window_s": seconds,
                "batches": batches, "max_batch": self.engine.max_batch,
                "batch_sizes": list(st.batch_sizes)[-batches:]
                if batches else [],
                "queue_wait_s": np.asarray(list(st.queue_wait_s)[-served:]
                                           if served else []),
                "model": self.cfg["model"], "index": self.cfg["index"],
                "stored_vectors": self.stored_vectors,
                "mean_stored_len": self.stored_vectors / self.n_docs}

    def close(self) -> int:
        """Stop serving, read the peak, free the program's state."""
        self.engine.stop()
        peak = harness.peak_bytes(self.ctx.devices)
        self.engine = None
        self.retriever = None
        gc.collect()
        return peak


def live_arrays(dev, top: int = 12) -> str:
    """The device's live arrays grouped by shape and dtype, largest
    first: what the bytes held after set-up are."""
    import jax
    groups, total = {}, 0
    for a in jax.live_arrays():
        if dev not in a.devices():
            continue
        key = f"{a.dtype}{list(a.shape)}"
        groups[key] = groups.get(key, 0) + int(a.nbytes)
        total += int(a.nbytes)
    rows = sorted(groups.items(), key=lambda kv: -kv[1])[:top]
    return f"total {total}; " + ", ".join(f"{k} {v}" for k, v in rows)


def sample_requests(done: list, n: int, rng, q_tokens) -> list:
    """A seeded sample of ``n`` served requests that holds the one with
    the longest query."""
    longest = max(range(len(done)),
                  key=lambda i: int((q_tokens[done[i][0]] > 0).sum()))
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in pick]


def check(served: Served, done: list) -> list:
    """The numbers compared for ``correct``. ``done`` holds (query row,
    scores [k], ids [k]) of requests served in the window."""
    cfg, ctx = served.cfg, served.ctx
    lim = cfg["limits"]
    chk = cfg["check"]
    sample = sample_requests(done, int(chk["requests"]),
                             np.random.default_rng([ctx.seed, 5]),
                             served.q_tokens)
    n_docs = served.corpus.n_docs
    bad = 0                 # -1 pads a short candidate set: not bad
    for _, _, ids in sample:
        live = ids[ids >= 0]
        bad += int((live >= n_docs).sum()) + len(live) - len(set(live))
        bad += int((ids < -1).sum())
    cast = control_dtype(ctx)
    got = {"bad_ids": float(bad)}
    got.update(recall_gaps(served, sample, cast))
    got["query_cos_gap"] = query_gap(served, int(chk["query_rows"]))
    if cast is not None:
        got["control_query_cos_gap"] = query_gap(
            served, int(chk["query_rows"]), cast)
    ctx.log("readings: " + json.dumps(got))
    judged = "control_" if cast is not None else ""
    return [harness.Check("bad_ids", got["bad_ids"], float(lim["bad_ids"]))
            ] + [harness.Check(k, got[judged + k], float(lim[k]))
                 for k in ("query_cos_gap", "recall_gap")]


def control_dtype(ctx):
    """The lower precision the control computes the reference in, when
    this run is the control (``--control``); else None."""
    name = ctx.options.get("control")
    if not name:
        return None
    import jax.numpy as jnp
    return jnp.dtype(name)


def query_gap(served: Served, rows: int, cast=None) -> float:
    """Largest per-token cosine shortfall between the query vectors the
    window's encoder produced (a seeded sample of its batches) and the
    float32 reference's; with ``cast``, the lower-precision reference's
    instead of the program's."""
    m = served.cfg["model"]
    toks = np.concatenate([np.asarray(t) for t, _ in served.captured.items])
    got = np.concatenate([np.asarray(v) for _, v in served.captured.items])
    toks, got = toks[:rows], got[:rows]
    want = ref.encode_queries(served.params, m, toks)
    if cast is not None:
        got = ref.encode_queries(served.params, m, toks, cast=cast)
    cos = (got.astype(np.float64) * want).sum(-1)
    return float(1.0 - cos.min())


def recall_gaps(served: Served, sample: list, cast=None) -> dict:
    """``recall_gap`` of the sampled requests (see module doc); with
    ``cast`` also ``control_recall_gap``: the lower-precision
    reference's top-k within the same pools, judged the same way."""
    cfg, ctx = served.cfg, served.ctx
    m = cfg["model"]
    factor = int(cfg["pooling"]["factor"])
    n_docs = served.corpus.n_docs
    rng = np.random.default_rng([ctx.seed, 6])
    common = rng.choice(n_docs, min(int(cfg["check"]["pool_docs"]), n_docs),
                        replace=False)
    rows = np.array([r for r, _, _ in sample])
    pools = [np.unique(np.concatenate([
        common, [served.q_src[r]], ids[(ids >= 0) & (ids < n_docs)]]))
        for r, _, ids in sample]
    every = np.unique(np.concatenate(pools))
    params = served.params
    q = ref.encode_queries(params, m, served.q_tokens[rows])
    pooled = pooled_docs(params, m, served.corpus, every, factor)
    k = served.k
    ranked = [pool[np.argsort([-ref.maxsim(q[i], pooled[int(d)])
                               for d in pool], kind="stable")[:k]]
              for i, pool in enumerate(pools)]

    def gap(answers):
        rec = [len(set(a.tolist()) & set(t.tolist())) / len(t)
               for a, t in zip(answers, ranked)]
        return float(1.0 - np.mean(rec))
    out = {"recall_gap": gap([ids for _, _, ids in sample])}
    if cast is not None:
        q_c = ref.encode_queries(params, m, served.q_tokens[rows], cast=cast)
        pooled_c = pooled_docs(params, m, served.corpus, every, factor,
                               cast=cast)
        out["control_recall_gap"] = gap([
            pool[np.argsort([-ref.maxsim(q_c[i], pooled_c[int(d)])
                             for d in pool], kind="stable")[:k]]
            for i, pool in enumerate(pools)])
    return out


def pooled_docs(params, m: dict, corpus, ids, factor: int, cast=None,
                chunk: int = 64) -> dict:
    """{doc id: reference Ward-pooled vectors} for docs ``ids``."""
    out = {}
    for lo in range(0, len(ids), chunk):
        part = np.asarray(ids[lo:lo + chunk])
        # one shape for every chunk: pad with the chunk's first doc
        padded = np.concatenate([part, np.full(chunk - len(part), part[0])])
        vecs = ref.encode_docs(params, m, corpus.doc_tokens(padded),
                               cast=cast)
        for d, v in zip(part, vecs):
            out[int(d)] = ref.ward(v, factor)
    return out


def results(pairs) -> list:
    """(query row, scores [k], ids [k]) of each resolved single-query
    request among ``(row, future)`` pairs."""
    out = []
    for r, f in pairs:
        if f.done() and f._error is None:
            S, I = f.result(0)
            out.append((int(r), S[0], I[0]))
    return out


def wait_all(futs: list, timeout_s: float = 60.0) -> None:
    """Block until every future has resolved, or ``timeout_s`` passes."""
    end = time.perf_counter() + timeout_s
    for f in futs:
        f._event.wait(max(end - time.perf_counter(), 0.0))
