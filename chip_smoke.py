"""Chip smoke: the full-width ColBERTv2 build -> Ward pool -> PLAID serve
path on a TPU, end to end, through the public entry points.

    python chip_smoke.py                 # one chip: build, checks, serve
    python chip_smoke.py --chips 4       # replica router across 4 chips

One chip: encode a seeded scifact-shaped synthetic corpus with the
published ColBERTv2 encoder (12 layers, d_model 768, 128-d vectors,
doc_maxlen 256; seeded random weights) in bf16, Ward-pool at factor 2,
build a 2-bit PLAID index and an exact flat twin from the same encode
(``repro.Retriever``), prove the Pallas kernels ran (no interpret mode,
no silent host fallback), check the encoder, the flat top-10 and the
PLAID scores against plain f32 references, then serve closed-loop
batches through ``Searcher`` and an open loop through ``ServingEngine``.

``--chips 4`` runs only the multi-chip paths and what they are compared
with: the flat ``shard_map`` scan over a 4-device ("shard",) mesh and
PLAID placed dispatch with 4 replicas behind the engine, each against
the one-lane result, plus a check that every lane's arrays sit on its
own device.

Exits non-zero on any failed check, and when JAX finds no TPU. The last
line of stdout is ``{"ok": true, "device": {...}}``. Everything runs in
this one process (a chip belongs to one process at a time). Per-phase
and compile seconds are set-up figures, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SCRATCH = ROOT / ".chip_smoke"          # git-ignored; removed at exit

# Reference tolerances (the reasoning is in CHANGES.md, PR 11):
# a default-precision f32 matmul on the TPU rounds both operands to bf16
# (unit roundoff 2^-8), so one token similarity of unit vectors is off
# by at most 2 * 2^-8; a max keeps that bound and the sum over the 32
# query tokens multiplies it by 32.
SCORE_TOL = 32 * 2 * 2.0 ** -8          # 0.25, worst case per MaxSim score
ENC_MEAN_COS, ENC_MIN_COS = 0.99, 0.95  # bf16 encoder vs f32 HIGHEST


class Checks:
    """Named pass/fail results; every phase runs, failures surface at
    the end (one chip run shows every broken check at once)."""

    def __init__(self):
        self.failed = []

    def __call__(self, name, ok, detail=""):
        print(f"  [{'ok' if ok else 'FAIL'}] {name}" +
              (f": {detail}" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok


class Clock:
    """Per-phase wall seconds, plus the XLA compile seconds inside each
    phase (``backend_compile_duration`` events — a persistent-cache hit
    shows up as a short retrieval instead of a compile)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.phases = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    @contextlib.contextmanager
    def phase(self, name):
        t0, c0 = time.perf_counter(), self.compile_s
        print(f"== {name}")
        yield
        wall, comp = time.perf_counter() - t0, self.compile_s - c0
        self.phases[name] = (wall, comp)
        print(f"   {name}: {wall:.1f} s (compile {comp:.1f} s)")


def require_tpu(jax):
    """The TPU devices, or None (and a message) when there are none."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devs[0].platform!r}); this smoke runs only on a chip")
        return None
    return devs


def has_kernel(jitted, *args, **static):
    """True when the compiled program holds a Mosaic kernel — proof that
    the Pallas path ran compiled, not in interpret mode or as its jnp
    reference."""
    return "tpu_custom_call" in jitted.lower(*args, **static).compile(
    ).as_text()


def shapes(*arrays):
    import jax
    return [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]


# --------------------------------------------------------------- references
def ref_maxsim(q, docs):
    """Exact float64 MaxSim of one query q [Lq, dim] (all tokens valid)
    against each doc of ``docs`` (list of [n_i, dim]) -> [n_docs]."""
    flat = np.concatenate(docs).astype(np.float64)
    starts = np.concatenate([[0], np.cumsum([len(d) for d in docs])[:-1]])
    sim = q.astype(np.float64) @ flat.T                  # [Lq, V]
    return np.maximum.reduceat(sim, starts, axis=1).sum(axis=0)


def decode_np(plaid, doc_ids):
    """Plain numpy decode of stored PLAID docs (centroid + residual
    bucket value, renormalized) — independent of the jnp/Pallas code."""
    codec = plaid.codec
    cent = np.asarray(codec.centroids, np.float32)
    vals = np.asarray(codec.values, np.float32)
    bits, dim = codec.bits, cent.shape[1]
    cpw = 32 // bits
    out = []
    for d in doc_ids:
        lo, hi = plaid.doc_offsets[d], plaid.doc_offsets[d + 1]
        w = np.asarray(plaid.codes[lo:hi], np.uint32)
        shifts = np.arange(cpw, dtype=np.uint32) * bits
        codes = ((w[:, :, None] >> shifts) & ((1 << bits) - 1)
                 ).reshape(len(w), dim)
        v = cent[plaid.assignments[lo:hi]] + vals[np.arange(dim), codes]
        out.append(v / np.maximum(np.linalg.norm(v, axis=-1,
                                                 keepdims=True), 1e-9))
    return out


# -------------------------------------------------------------------- build
def build(args, cfg, clock, checks):
    """Encode once, pool + index twice (PLAID and the flat twin)."""
    import jax
    from repro.api import Retriever
    from repro.core.spec import IndexSpec, PoolingSpec, RetrieverSpec
    from repro.data.corpus import DATASET_SPECS, SyntheticRetrievalCorpus
    from repro.models.colbert import init_colbert
    from repro.retrieval.indexer import EncodedDocs

    t = cfg.trunk
    print(f"encoder {cfg.name}: {t.n_layers} layers, d_model {t.d_model}, "
          f"{t.n_heads} heads, d_ff {t.d_ff}, vocab {t.vocab_size}, "
          f"proj {cfg.proj_dim}, doc_maxlen {cfg.doc_maxlen}, "
          f"query_maxlen {cfg.query_maxlen}, compute {t.dtype}")
    with clock.phase("setup"):
        params = init_colbert(jax.random.PRNGKey(args.seed), cfg)
        spec = DATASET_SPECS["scifact"]
        corpus = SyntheticRetrievalCorpus(
            type(spec)(**{**spec.__dict__, "n_docs": args.docs,
                          "n_queries": args.queries,
                          "seed": spec.seed + args.seed}),
            vocab_size=t.vocab_size)
        doc_toks = corpus.doc_token_batch(cfg.doc_maxlen - 2)
        q_toks = corpus.query_token_batch(cfg.query_maxlen - 2)
    with clock.phase("encode"):
        enc = EncodedDocs.encode(params, cfg, doc_toks)
        jax.block_until_ready(enc.batches[-1][0])
    index = dict(n_centroids=256, nprobe=2, t_cs=0.45, ndocs=256)
    pooling = PoolingSpec(method="ward", factor=2)
    built = {}
    for backend in ("plaid", "flat"):
        with clock.phase(f"pool+index ({backend})"):
            r = Retriever.build(params, cfg, enc, RetrieverSpec(
                pooling=pooling,
                index=IndexSpec.from_config(cfg, backend=backend, **index)))
        s = r.stats
        print(f"   {backend}: {s.n_docs} docs, {s.n_vectors_raw} raw -> "
              f"{s.n_vectors_stored} stored vectors "
              f"(vector_reduction {s.vector_reduction:.3f}), index "
              f"{s.index_bytes} bytes, device {s.device_bytes} bytes")
        built[backend] = r
    s = built["plaid"].stats
    checks("ward f=2 stores ~half the vectors",
           0.40 <= s.vector_reduction <= 0.55, f"{s.vector_reduction:.3f}")
    checks("plaid and flat twins store the same vectors",
           built["flat"].stats.n_vectors_stored == s.n_vectors_stored)
    from repro.core.pooling import pool_doc_embeddings
    from repro.kernels.ward_pool.ops import resolve_impl
    v, emit, _ = enc.batches[0]
    checks("ward step ran the compiled Pallas kernel",
           resolve_impl(pooling.ward_kernel) == "kernel" and has_kernel(
               pool_doc_embeddings, *shapes(v, emit), factor=2,
               method="ward"))
    return params, corpus, q_toks, enc, built


def encoder_check(params, cfg, q_toks, doc_toks, checks):
    """bf16 forward vs the same weights in f32 at HIGHEST precision."""
    import dataclasses
    import jax
    from repro.models.colbert import encode_docs, encode_queries
    cfg32 = dataclasses.replace(
        cfg, trunk=dataclasses.replace(cfg.trunk, dtype="float32"))
    cos = []
    for fn, toks in ((encode_queries, q_toks), (encode_docs, doc_toks)):
        v, m = fn(params, toks, cfg)
        with jax.default_matmul_precision("highest"):
            v32, _ = fn(params, toks, cfg32)
        c = np.sum(np.asarray(v) * np.asarray(v32), axis=-1)
        cos.append(c[np.asarray(m)])
    cos = np.concatenate(cos)
    checks("encoder bf16 vs f32 HIGHEST, per-token cosine",
           cos.mean() >= ENC_MEAN_COS and cos.min() >= ENC_MIN_COS,
           f"mean {cos.mean():.5f} (>= {ENC_MEAN_COS}), "
           f"min {cos.min():.5f} (>= {ENC_MIN_COS})")


def reference_checks(built, Q, checks, k=10):
    """Flat top-k vs numpy brute force; PLAID scores vs exact f32 MaxSim
    over its decoded stored vectors; PLAID recall@k against flat."""
    flat, plaid = built["flat"].index, built["plaid"].index
    fs, fi = flat.search_batch(Q, k=k)
    ps, pi = plaid.search_batch(Q, k=k)
    docs = flat.store.docs_list()
    f_err, f_slack, overlap, p_err, recall = 0.0, 0.0, [], 0.0, []
    for q in range(len(Q)):
        ref = ref_maxsim(Q[q], docs)
        top = np.argsort(-ref, kind="stable")[:k]
        f_err = max(f_err, float(np.abs(fs[q] - ref[fi[q]]).max()))
        # every returned doc must be a true top-k doc up to the bound
        f_slack = max(f_slack, float(ref[top[-1]] - ref[fi[q]].min()))
        overlap.append(len(set(fi[q]) & set(top)) / k)
        live = pi[q] >= 0
        exact = np.array([ref_maxsim(Q[q], [d])[0] for d in
                          decode_np(plaid._plaid, pi[q][live])])
        p_err = max(p_err, float(np.abs(ps[q][live] - exact).max()))
        recall.append(len(set(pi[q][live]) & set(fi[q])) / k)
    checks("flat top-10 scores vs numpy f32 brute force", f_err <= SCORE_TOL,
           f"max |err| {f_err:.6f} (<= {SCORE_TOL})")
    checks("flat top-10 ids are true top-10 up to the score bound",
           f_slack <= 2 * SCORE_TOL,
           f"worst slack {f_slack:.6f} (<= {2 * SCORE_TOL}); id overlap "
           f"with the exact top-10 {np.mean(overlap):.3f}")
    checks("plaid scores vs exact f32 MaxSim over decoded vectors",
           p_err <= SCORE_TOL, f"max |err| {p_err:.6f} (<= {SCORE_TOL})")
    print(f"   plaid recall@{k} against flat: {np.mean(recall):.3f} "
          f"over {len(Q)} queries")
    return (fs, fi), (ps, pi)


def paths_engaged(built, Q, checks):
    """The device candidate path and the packed rerank compiled with
    their Mosaic kernels (no host fallback, no interpret mode)."""
    import jax
    import jax.numpy as jnp
    from repro.core import plaid as P
    from repro.kernels.maxsim_packed.ops import maxsim_packed_rerank
    ix = built["plaid"].index
    p = ix._plaid
    Nq, Lq, dim = Q.shape
    use_dev, geom = P.device_probe_plan(p, Lq, ix.nprobe, ix.ndocs,
                                        ix.probe_kernel, t_cs=ix.t_cs)
    checks("device_probe_plan engages the device candidate path", use_dev)
    if not use_dev:
        return
    div, kk, c_score, s_out = geom
    cents = jnp.asarray(p.codec.centroids)
    f32 = jax.ShapeDtypeStruct
    checks("serve step (stages 1-3) holds the plaid_probe kernel",
           has_kernel(P._device_candidates,
                      f32((Nq, Lq, cents.shape[0]), jnp.float32),
                      f32((Nq, Lq), jnp.bool_),
                      *shapes(div.doc_member, ix._live_dev()),
                      k=kk, t_cs=float(ix.t_cs), ndocs=int(ix.ndocs),
                      c_score=c_score, s_out=s_out, impl="kernel"))
    ids, words, _ = p.padded_packed()
    L, W = ids.shape[1], words.shape[2]
    checks("packed rerank runs maxsim_packed",
           ix.packed_rerank and p.recon is None and has_kernel(
               maxsim_packed_rerank, f32((Nq, Lq, dim), jnp.float32),
               f32((Nq, Lq), jnp.bool_), f32((Nq, s_out, L, W), words.dtype),
               f32((Nq, s_out, L), jnp.int32), f32((Nq, s_out, L), jnp.bool_),
               *shapes(cents, jnp.asarray(p.codec.values)),
               bits=p.codec.bits))


def ward_long_contract(seed=0, n_docs=4, N=2048, dim=128, factor=2):
    """The long-doc Ward kernel against ``core/ward.py`` at N = 2048 on
    seeded clustered token vectors (2046 valid, the long-doc cap, and a
    shorter doc), and its rows read again per merge."""
    import jax.numpy as jnp
    from repro.kernels.ward_pool import ward_assign
    from repro.kernels.ward_pool.ops import ward_rescans
    rng = np.random.default_rng(seed)
    cent = rng.normal(size=(n_docs, 96, dim)).astype(np.float32)
    x = (cent[np.arange(n_docs)[:, None], rng.integers(0, 96, (n_docs, N))]
         + 0.6 * rng.normal(size=(n_docs, N, dim)).astype(np.float32))
    n_valid = np.full(n_docs, N - 2)
    n_valid[-1] = N // 3
    emit = jnp.asarray(np.arange(N)[None, :] < n_valid[:, None])
    a_k, rescans = (np.asarray(a) for a in ward_rescans(x, emit, factor))
    a_r = np.asarray(ward_assign(jnp.asarray(x), emit, factor, impl="ref"))
    merges = int((n_valid - (n_valid // factor + 1)).sum())
    print(f"   long-doc ward kernel == core/ward.py at N = {N}: bitwise "
          f"{np.array_equal(a_k, a_r)} ({int((a_k == a_r).all(axis=1).sum())}"
          f"/{n_docs} docs equal); rescans per merge "
          f"{rescans.sum() / merges:.3f} ({int(rescans.sum())} over "
          f"{merges} merges)")


def contracts(built, first_batch, Q):
    """The CPU suite's bitwise contracts, measured on this chip: reported,
    not enforced (the chip's matmuls need not reproduce the CPU's bits)."""
    from repro.kernels.ward_pool import ward_assign
    v, emit = first_batch
    a_k = np.asarray(ward_assign(v, emit, 2, impl="kernel"))
    a_r = np.asarray(ward_assign(v, emit, 2, impl="ref"))
    print(f"   ward kernel == core/ward.py: bitwise {np.array_equal(a_k, a_r)}"
          f" ({int((a_k == a_r).all(axis=1).sum())}/{len(a_k)} docs equal)")
    ward_long_contract()
    ix = built["plaid"].index
    packed = ix.search_batch(Q, k=10)
    for name, attr, alt in (("packed rerank == f32 recon rerank",
                             "packed_rerank", False),
                            ("device probe == host probe", "probe_kernel",
                             "host")):
        keep = getattr(ix, attr)
        setattr(ix, attr, alt)
        other = ix.search_batch(Q, k=10)
        setattr(ix, attr, keep)
        print(f"   {name}: ids equal {np.array_equal(packed[1], other[1])}"
              f", scores bitwise {np.array_equal(packed[0], other[0])}, max "
              f"|diff| {np.abs(packed[0] - other[0]).max():.3g} over "
              f"{len(Q)} queries")


def serve(built, q_toks, args, checks):
    from repro.core.spec import ServeSpec
    from repro.launch.serve import closed_loop, open_loop
    r = built["plaid"]
    print("-- closed loop through Searcher (plaid)")
    rows = closed_loop(r.searcher, r.index, q_toks, [1, 8, 32],
                       args.serve_queries, 10)
    print("-- open loop through ServingEngine (plaid)")
    rows += open_loop(r.searcher, r.index, q_toks, [args.rate],
                      args.serve_queries, ServeSpec(max_batch=32), None,
                      None)
    checks("no failed query", all(row.get("errors", 0) == 0
                                  for row in rows))
    checks("no compile inside a timed window",
           all(row["compiles"] == 0 for row in rows),
           str([row["compiles"] for row in rows]))


def one_chip(args, cfg, clock, checks):
    params, corpus, q_toks, enc, built = build(args, cfg, clock, checks)
    first_batch = enc.batches[0][:2]
    del enc
    with clock.phase("reference checks"):
        encoder_check(params, cfg, q_toks[:4],
                      corpus.doc_token_batch(cfg.doc_maxlen - 2)[:2], checks)
        Q = built["plaid"].searcher.encode_queries(q_toks[:8])
        reference_checks(built, Q, checks)
    with clock.phase("paths engaged"):
        paths_engaged(built, Q, checks)
    with clock.phase("bitwise contracts"):
        contracts(built, first_batch, Q)
    with clock.phase("serve"):
        serve(built, q_toks, args, checks)


# -------------------------------------------------------------- four chips
def four_chips(args, cfg, clock, checks, devs):
    """Flat shard_map scan and PLAID 4-replica routing vs one lane."""
    from repro.core.index import MultiVectorIndex
    from repro.core.replicated import ReplicatedIndex
    from repro.core.sharded import ShardedIndex
    from repro.launch.engine import ServingEngine
    from repro.retrieval.searcher import Searcher
    params, corpus, q_toks, enc, built = build(args, cfg, clock, checks)
    del enc
    n = len(devs)
    flat, plaid = built["flat"].index, built["plaid"].index
    Q = built["plaid"].searcher.encode_queries(q_toks[:32])
    with clock.phase("one lane"):
        f1 = flat.search_batch(Q, k=10)
        p1 = plaid.search_batch(Q, k=10)

    with clock.phase(f"flat shard_map over {n} devices"):
        docs = flat.store.docs_list()
        shards, bases, lo = [], [], 0
        for part in np.array_split(np.arange(len(docs)), n):
            s = MultiVectorIndex(dim=flat.dim, backend="flat",
                                 doc_maxlen=flat.doc_maxlen)
            s.add([docs[i] for i in part])
            shards.append(s)
            bases.append(lo)
            lo += len(part)
        rep = ReplicatedIndex.replicate(ShardedIndex.from_parts(shards,
                                                                bases), 1)
        plan = rep._plan_for(0)
        placed = (set() if plan is None else
                  {s.device for s in plan.d.addressable_shards})
        checks("flat scan compiles to one shard_map program over a "
               f"{n}-device mesh", plan is not None and len(placed) == n,
               f"doc shards on {sorted(d.id for d in placed)}")
        fn = rep.search_batch(Q, k=10)
        checks("shard_map flat == one-lane flat",
               np.array_equal(fn[1], f1[1]) and np.allclose(fn[0], f1[0],
                                                            atol=1e-5),
               f"bitwise scores: {np.array_equal(fn[0], f1[0])}")

    with clock.phase(f"plaid {n} replicas through the engine"):
        path = SCRATCH / "plaid_index"
        built["plaid"].save(str(path))
        rep = ReplicatedIndex.from_dir(str(path), n_replicas=n)
        lanes = [row[0] for row in rep.device_table]
        checks(f"{n} replica lanes on distinct devices",
               len({d.id for d in lanes}) == n,
               f"lanes on {[d.id for d in lanes]}")
        for r in range(n):
            same = rep.search_batch_on(r, Q, k=10)
            checks(f"lane {r} == one-lane plaid",
                   np.array_equal(same[1], p1[1])
                   and np.allclose(same[0], p1[0], atol=1e-5),
                   f"bitwise scores: {np.array_equal(same[0], p1[0])}")
        # every lane is already warm at this shape (search_batch_on)
        eng = ServingEngine(Searcher(params, cfg, rep), max_batch=32, k=10,
                            n_replicas=n, warmup_on_start=False)
        with eng:
            futs = [eng.submit(q_toks[:32]) for _ in range(4 * n)]
            res = [f.result(timeout=300) for f in futs]
        snap = eng.stats.snapshot()
        want = built["plaid"].search(q_toks[:32], k=10)
        checks("engine results over the lanes == one-lane search",
               all(np.array_equal(I, want[1]) for _, I in res))
        checks("the router spread batches over lanes",
               len(snap["replica_batches"]) >= 2,
               f"batches per lane {snap['replica_batches']}")
        checks("no failed query", snap["failed"] == 0)
        for r, inner in enumerate(rep._inners):
            # the lane's caches as its searches left them (built lazily
            # under the lane's device; None would mean never built)
            p = inner._plaid
            arrays = [p.codec.centroids, p.codec.values,
                      inner._live_dev_cache, *(p._packed_padded or [None]),
                      p._device_ivf and p._device_ivf.doc_member]
            where = {d.id for a in arrays if a is not None
                     for d in a.devices()}
            where |= {"unbuilt"} if any(a is None for a in arrays) else set()
            checks(f"lane {r} arrays resident on its own device",
                   where == {lanes[r].id}, f"devices {sorted(where)}")
        rep.close()


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the replica-router paths, on 4 chips")
    ap.add_argument("--docs", type=int, default=8192)
    ap.add_argument("--queries", type=int, default=128,
                    help="query pool drawn from the corpus")
    ap.add_argument("--serve-queries", type=int, default=256,
                    help="queries served per closed-loop row / open loop")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="open-loop offered queries per second")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: the repro package is not next to this script "
              f"({ROOT / 'src'}); run it from a checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = require_tpu(jax)
    if devs is None:
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}")
        return 1
    devs = devs[:args.chips]
    print(f"platform {devs[0].platform}, device_kind {devs[0].device_kind}, "
          f"device count {len(jax.devices())}, compile cache {cache}")
    from repro.launch.serve import model_config
    cfg = model_config("colbertv2")
    clock, checks = Clock(jax), Checks()
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(args, cfg, clock, checks)
        else:
            four_chips(args, cfg, clock, checks, devs)
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    for d in devs:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: peak HBM {stats.get('peak_bytes_in_use', 0)}"
              f" bytes of {stats.get('bytes_limit', 0)}")
    print(f"total {time.perf_counter() - t0:.1f} s, of which compile "
          f"{clock.compile_s:.1f} s")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
