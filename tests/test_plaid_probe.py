"""Device-resident PLAID candidate generation (PR 9): the fused probe
kernel + on-device IVF gather must be indistinguishable from the host
reference path — same survivor doc ids, same survivor ORDER, same
validity mask, bitwise-identical final search scores — monolithic,
sharded, and replicated, while the transfer-guard proves the device
pipeline moves zero bytes device->host between the encoded queries and
the final top-k.

Also pins the PR's satellite bugfix: a fully-masked query token used to
probe anyway (``top_k`` over an all--inf centroid row picks centroids
0..nprobe-1 and walks their lists into the candidate set); masked
tokens must now contribute ZERO candidates on both paths.

Hypothesis sweep gated on ``hypothesis`` (PR 1 convention: skip, don't
fail, in containers without it; CI installs it).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.index import MultiVectorIndex
from repro.core.ivf import build_device_inverted_lists
from repro.core.plaid import device_probe_plan, plaid_candidates
from repro.core.replicated import ReplicatedIndex
from repro.core.sharded import ShardedIndex

DIM = 16
KW = dict(doc_maxlen=24, n_centroids=16)


def unit_docs(rng, n=40, dim=DIM, lo=4, hi=20):
    docs = []
    for _ in range(n):
        v = rng.normal(size=(rng.integers(lo, hi), dim)).astype(np.float32)
        docs.append(v / np.linalg.norm(v, axis=-1, keepdims=True))
    return docs


def unit_queries(rng, n, lq=5, dim=DIM):
    q = rng.normal(size=(n, lq, dim)).astype(np.float32)
    return q / np.linalg.norm(q, axis=-1, keepdims=True)


def build(rng, n=40, ndocs=16, **over):
    kw = dict(KW, ndocs=ndocs)
    kw.update(over)
    idx = MultiVectorIndex(dim=DIM, backend="plaid", **kw)
    idx.add(unit_docs(rng, n=n))
    return idx


def survivors(cand, mask):
    """Per-row (ordered survivor ids, count) — the candidate contract:
    pad geometry may differ between paths, survivors must not."""
    cand, mask = np.asarray(cand), np.asarray(mask)
    return [cand[r][mask[r]].tolist() for r in range(len(cand))]


def assert_candidates_equal(idx, qs, q_mask=None):
    """Host vs device candidates + bitwise search parity on one index.

    Returns False (without asserting) when ``device_probe_plan``
    declines the geometry — the caller decides whether engagement is
    required for its cell.
    """
    use_dev, _ = device_probe_plan(idx._plaid, np.asarray(qs).shape[1],
                                   idx.nprobe, idx.ndocs, "device",
                                   t_cs=idx.t_cs)
    idx.probe_kernel = "host"
    c0, m0 = idx.candidates(qs, q_mask=q_mask)
    S0, I0 = idx.search_batch(qs, k=7, q_mask=q_mask)
    if not use_dev:
        return False
    idx.probe_kernel = "device"
    c1, m1 = idx.candidates(qs, q_mask=q_mask)
    S1, I1 = idx.search_batch(qs, k=7, q_mask=q_mask)
    idx.probe_kernel = "auto"
    assert isinstance(c1, jax.Array), "device path returned host arrays"
    assert survivors(c0, m0) == survivors(c1, m1)
    np.testing.assert_array_equal(I0, I1)
    assert np.array_equal(np.asarray(S0, np.float32).view(np.int32),
                          np.asarray(S1, np.float32).view(np.int32)), \
        "scores drifted bitwise between device and host candidate paths"
    return True


# ------------------------------------------------------------------ parity
# (ndocs, corpus) pairs where the plan's static proof engages: a tight
# budget on a small corpus (runtime prune branch) and a loose budget on
# a corpus wide enough that the gather ladder stays below n_docs
# (runtime unpruned branch) — both sides of the traced lax.cond
@pytest.mark.parametrize("ndocs,n", [(8, 40), (64, 200)])
@pytest.mark.parametrize("nprobe", [1, 4])
def test_device_matches_host_monolithic(nprobe, ndocs, n):
    rng = np.random.default_rng(nprobe * 100 + ndocs)
    idx = build(rng, n=n, ndocs=ndocs, nprobe=nprobe)
    assert assert_candidates_equal(idx, unit_queries(rng, 6)), \
        "device path must engage on this geometry"


def test_device_matches_host_with_deletes_and_adds():
    """Parity must survive the mutation path: add/delete invalidate the
    cached device IVF + live mask, and deleted docs never reappear."""
    rng = np.random.default_rng(7)
    idx = build(rng, n=40)
    qs = unit_queries(rng, 4)
    assert assert_candidates_equal(idx, qs)
    idx.delete([0, 5, 11])
    assert assert_candidates_equal(idx, qs)
    idx.probe_kernel = "device"
    c, m = idx.candidates(qs)
    for row in survivors(c, m):
        assert not {0, 5, 11} & set(row)
    idx.add(unit_docs(rng, n=6))
    idx.probe_kernel = "auto"
    assert assert_candidates_equal(idx, qs)


def test_single_centroid_and_empty_lists():
    """Edges: K=1 (every token probes the one list) and K >> vectors
    (most IVF lists empty; probed empty lists add nothing)."""
    rng = np.random.default_rng(11)
    one = build(rng, n=100, n_centroids=1, nprobe=4)
    assert assert_candidates_equal(one, unit_queries(rng, 3))
    # guaranteed-empty list: docs biased to the +x0 half-space, codec
    # centroid 0 pinned at -x0 — max-cosine assignment never picks it,
    # while unbiased queries still probe it
    from repro.core.ivf import train_centroids
    from repro.core.quantization import train_codec
    docs = []
    for _ in range(40):
        v = rng.normal(size=(rng.integers(4, 20), DIM)).astype(np.float32)
        v[:, 0] += 3.0
        docs.append(v / np.linalg.norm(v, axis=-1, keepdims=True))
    flat = np.concatenate(docs)
    far = np.zeros((1, DIM), np.float32)
    far[0, 0] = -1.0
    cents = np.concatenate([far, np.asarray(train_centroids(flat, 15))])
    sparse = MultiVectorIndex(dim=DIM, backend="plaid", nprobe=8,
                              **dict(KW, ndocs=16))
    sparse.set_codec(train_codec(flat, cents, bits=2))
    sparse.add(docs)
    assert (np.diff(sparse._plaid.ivf.offsets) == 0).any(), \
        "edge not exercised: no empty IVF list"
    assert assert_candidates_equal(sparse, unit_queries(rng, 3))


# ------------------------------------------------------- masked-token pin
def test_fully_masked_token_adds_zero_candidates():
    """Satellite bugfix pin: a masked query token must contribute ZERO
    candidates. The query is built so the masked token is the ONLY one
    near its nearest centroids — before the fix, ``top_k`` over its
    all--inf score row probed centroids 0..nprobe-1 regardless, leaking
    their lists into the candidate set on both paths."""
    rng = np.random.default_rng(23)
    idx = build(rng, n=40, nprobe=2)
    qs = unit_queries(rng, 2, lq=6)
    masked = np.ones((2, 6), bool)
    masked[:, -1] = False
    assert device_probe_plan(idx._plaid, 6, idx.nprobe, idx.ndocs,
                             "device", t_cs=idx.t_cs)[0]
    for pk in ("host", "device"):
        idx.probe_kernel = pk
        c_full, m_full = idx.candidates(qs[:, :5], q_mask=None)
        c_mask, m_mask = idx.candidates(qs, q_mask=masked)
        assert survivors(c_full, m_full) == survivors(c_mask, m_mask), \
            f"{pk}: masked token changed the candidate set"
    idx.probe_kernel = "auto"


def test_fully_masked_query_has_no_candidates():
    """A row whose tokens are ALL masked yields an empty candidate set
    (and -inf/-1 search results) on both paths."""
    rng = np.random.default_rng(29)
    idx = build(rng, n=40)
    qs = unit_queries(rng, 3)
    qm = np.ones(qs.shape[:2], bool)
    qm[1] = False
    for pk in ("host", "device"):
        idx.probe_kernel = pk
        c, m = idx.candidates(qs, q_mask=qm)
        rows = survivors(c, m)
        assert rows[1] == [], f"{pk}: fully-masked query gained candidates"
        assert rows[0] and rows[2]
        S, I = idx.search_batch(qs, k=5, q_mask=qm)
        assert (np.asarray(I)[1] == -1).all()
    idx.probe_kernel = "auto"


# ------------------------------------------------------------ device IVF
def test_device_ivf_overflow_accounting():
    """``list_cap`` truncation keeps each list's LOWEST doc ids, counts
    every drop in ``overflow``, and a capped (inexact) build disqualifies
    the device path via ``device_probe_plan``."""
    rng = np.random.default_rng(31)
    idx = build(rng, n=40, n_centroids=4)
    p = idx._plaid
    exact = build_device_inverted_lists(p.ivf, p.vec2doc, p.n_docs)
    assert exact.overflow == 0
    # padded view vs CSR ground truth, per centroid
    for c in range(p.ivf.n_centroids):
        want = np.unique(p.vec2doc[p.ivf.list_for(c)])
        row = np.asarray(exact.doc_lists[c])[np.asarray(exact.doc_valid[c])]
        np.testing.assert_array_equal(row, want)
        np.testing.assert_array_equal(
            np.flatnonzero(np.asarray(exact.doc_member[c])), want)
    capped = build_device_inverted_lists(p.ivf, p.vec2doc, p.n_docs,
                                         list_cap=2)
    assert capped.list_cap == 2 and capped.overflow > 0
    for c in range(p.ivf.n_centroids):
        want = np.unique(p.vec2doc[p.ivf.list_for(c)])[:2]
        row = np.asarray(capped.doc_lists[c])[np.asarray(capped.doc_valid[c])]
        np.testing.assert_array_equal(row, want)
    p._device_ivf = capped
    use_dev, _ = device_probe_plan(p, 5, idx.nprobe, idx.ndocs, "device",
                                   t_cs=idx.t_cs)
    assert not use_dev, "overflowed IVF must disqualify the device path"
    p._device_ivf = None


def test_device_bytes_counts_ivf_tables():
    rng = np.random.default_rng(37)
    idx = build(rng, n=20)
    p = idx._plaid
    base = p.device_bytes_detail()
    assert base["ivf"] == 0                 # lazy: not built yet
    div = p.device_ivf()
    detail = p.device_bytes_detail()
    assert detail["ivf"] == div.device_bytes() > 0
    assert p.device_bytes() == sum(detail.values())


# ----------------------------------------------------- sharded/replicated
def test_sharded_and_replicated_parity():
    """set_probe_kernel fans the runtime-only toggle across shards and
    replica lanes; every combination stays bitwise-identical."""
    rng = np.random.default_rng(41)
    docs = unit_docs(rng, n=120)
    qs = unit_queries(rng, 4)
    total = sum(len(d) for d in docs)
    cap = max(total // 3, max(len(d) for d in docs), 1)
    sh = ShardedIndex(dim=DIM, backend="plaid", shard_max_vectors=cap,
                      **dict(KW, ndocs=16))
    sh.add(docs)
    assert sh.n_shards >= 2
    sh.set_probe_kernel("host")
    S0, I0 = sh.search_batch(qs, k=8)
    sh.set_probe_kernel("device")
    assert any(device_probe_plan(s._plaid, qs.shape[1], s.nprobe,
                                 s.ndocs, "device", t_cs=s.t_cs)[0]
               for s in sh.shards)
    S1, I1 = sh.search_batch(qs, k=8)
    np.testing.assert_array_equal(I0, I1)
    assert np.array_equal(np.asarray(S0, np.float32).view(np.int32),
                          np.asarray(S1, np.float32).view(np.int32))
    rep = ReplicatedIndex.replicate(sh, 2)
    rep.set_probe_kernel("device")
    for r in range(2):
        S2, I2 = rep.search_batch_on(r, qs, k=8)
        np.testing.assert_array_equal(I0, I2)
    rep.set_probe_kernel("auto")


# ------------------------------------------------------------- zero hops
def test_zero_host_transfers_probe_to_rerank():
    """With the device path engaged, candidates -> rerank -> device
    top-k run under a device->host transfer guard: the only host copy
    is the final [Nq, k] result, taken after the guard exits."""
    rng = np.random.default_rng(43)
    idx = build(rng, n=40)
    idx.probe_kernel = "device"
    qs = unit_queries(rng, 4)
    idx.search_batch(qs, k=5)               # warm traces outside guard
    with jax.transfer_guard_device_to_host("disallow"):
        scores, cand = idx.scored_candidates(qs)
        top_s, top_i = jax.lax.top_k(scores, 5)
        top_ids = jnp.take_along_axis(cand, top_i, axis=1)
    jax.block_until_ready((top_s, top_ids))
    idx.probe_kernel = "auto"


def test_no_retrace_through_mixed_shape_stream():
    """One executable per (Nq, Lq): after warm_shapes, a mixed-batch
    stream through the device pipeline compiles NOTHING new."""
    from repro.launch.engine import CompileCounter
    rng = np.random.default_rng(47)
    idx = build(rng, n=40)
    idx.probe_kernel = "device"
    assert idx._probe_plan(5)[0]
    qa, qb = unit_queries(rng, 8), unit_queries(rng, 3)
    idx.warm_shapes(qa, k=5)
    idx.warm_shapes(qb, k=5)
    with CompileCounter() as c:
        for _ in range(3):
            idx.search_batch(qa, k=5)
            idx.search_batch(qb, k=5)
    assert c.count == 0, f"{c.count} re-traces in device probe stream"
    idx.probe_kernel = "auto"


# ------------------------------------------------------- kernel vs ref
def pruned_scores(rng, nq, lq, k, t_cs):
    """Random centroid scores pruned as the candidate program prunes
    them: masked query tokens -inf, then below ``t_cs`` -> 0."""
    cs = jnp.asarray(rng.normal(size=(nq, lq, k)) * 0.5, jnp.float32)
    qm = jnp.asarray(rng.random((nq, lq)) > 0.3).at[0, 0].set(False)
    csm = jnp.where(qm[:, :, None], cs, -jnp.inf)
    return jnp.where(csm >= t_cs, csm, 0.0)


@pytest.mark.parametrize("t_cs", [0.0, 0.3, 0.9])
def test_probe_kernel_matches_reference(t_cs):
    """Pallas bag kernel (interpret mode on CPU) vs its jnp doc-major
    reference, over ragged doc tiles (300 docs in tiles of 128), masked
    query tokens and docs with no centroid: bitwise equal (both sum the
    query tokens in ``fold_sum``'s order), and equal to the plain
    max-then-sum formula up to that order."""
    from repro.kernels.plaid_probe.kernel import plaid_probe_bag_pallas
    from repro.kernels.plaid_probe.ops import plaid_probe_bag_scores
    rng = np.random.default_rng(53)
    nq, lq, k, n = 3, 5, 16, 300
    member = rng.random((k, n)) < 0.2
    member[:, [0, 7, 299]] = False                  # empty docs
    member = jnp.asarray(member, jnp.float32)
    csp = pruned_scores(rng, nq, lq, k, t_cs)
    ref = np.asarray(plaid_probe_bag_scores(csp, member, impl="ref"))
    ker = np.asarray(plaid_probe_bag_pallas(csp, member, block_d=128,
                                            interpret=True))
    assert np.array_equal(ref.view(np.int32), ker.view(np.int32)), \
        "bag kernel drifted from its reference"
    assert (ref[:, [0, 7, 299]] == 0).all()
    plain = (np.asarray(csp)[:, :, :, None]
             * np.asarray(member)[None, None]).max(axis=2).sum(axis=1)
    np.testing.assert_allclose(ref, plain, rtol=1e-6, atol=0)


# ------------------------------------------------ bag scorer exactness
def indexes_of(kind, rng):
    """The PLAID indexes (one per shard) of a monolithic index, of one
    after deletes and adds, or of a sharded one."""
    if kind == "sharded":
        docs = unit_docs(rng, n=200)
        cap = max(sum(len(d) for d in docs) // 3,
                  max(len(d) for d in docs))
        sh = ShardedIndex(dim=DIM, backend="plaid", shard_max_vectors=cap,
                          **dict(KW, ndocs=16))
        sh.add(docs)
        assert sh.n_shards >= 2
        return [s._plaid for s in sh.shards]
    idx = build(rng, n=60, nprobe=4)
    if kind == "mutated":
        idx.delete([0, 5, 11, 40])
        idx.add(unit_docs(rng, n=8))
    return [idx._plaid]


@pytest.mark.parametrize("kind", ["monolithic", "mutated", "sharded"])
def test_bag_scores_equal_gathered_scores(kind):
    """The doc-major bag reference, read at the candidate slots, equals
    ``_approx_scores_batch`` over the gathered code rows bit for bit:
    per query token the max over a doc's centroid bag IS the max over
    its tokens, and both sum the tokens in ``fold_sum``'s order. The
    candidate program's prune branch returns the same survivors with the
    interpreted kernel as with the reference, and as the host path."""
    from repro.core.plaid import (_approx_scores_batch,
                                  _centroid_scores_batch,
                                  _device_candidates, _gather_candidates)
    from repro.kernels.plaid_probe.ref import plaid_probe_bag_ref
    rng = np.random.default_rng(59)
    qs = unit_queries(rng, 4, lq=6)
    qm = rng.random(qs.shape[:2]) > 0.3
    qm[:, 0] = True
    qm[2, 1:] = False                           # one single-token query
    engaged = 0
    for p in indexes_of(kind, rng):
        codes, tok_mask = p.padded_codes()
        div = p.device_ivf()
        cs = _centroid_scores_batch(jnp.asarray(qs), jnp.asarray(
            p.codec.centroids))
        csm = jnp.where(jnp.asarray(qm)[:, :, None], cs, -jnp.inf)
        nprobe = 4
        probe = np.asarray(jax.lax.top_k(csm, nprobe)[1])
        valid = np.broadcast_to(qm[:, :, None], probe.shape)
        cand, cmask = _gather_candidates(p, probe, None, valid)
        idx, cm = jnp.asarray(cand), jnp.asarray(cmask)
        gcodes = jnp.take(codes, idx, axis=0)
        gmask = jnp.take(tok_mask, idx, axis=0) & cm[:, :, None]
        for t_cs in (0.0, 0.3, 0.9):
            csp = jnp.where(csm >= t_cs, csm, 0.0)
            want = np.asarray(_approx_scores_batch(csm, gcodes, gmask, cm,
                                                   t_cs))
            bag = plaid_probe_bag_ref(csp, div.doc_member)
            got = np.asarray(jnp.where(
                cm, jnp.take_along_axis(bag, idx, axis=1), -jnp.inf))
            np.testing.assert_array_equal(got.view(np.int32),
                                          want.view(np.int32))
            # per token, bitwise: max over the bag vs over the tokens
            tok_bag = jnp.max(csp[:, :, :, None]
                              * div.doc_member[None, None], axis=2)
            tok_bag = np.asarray(jnp.take_along_axis(
                tok_bag, idx[:, None, :], axis=2))          # [Nq, Lq, C]
            vals = jax.vmap(lambda t, i: t[i])(jnp.swapaxes(csp, 1, 2),
                                               gcodes)  # [Nq, C, L, Lq]
            tok_rows = np.asarray(jnp.where(gmask[..., None], vals, 0.0
                                            ).max(axis=2))  # [Nq, C, Lq]
            tok_rows = np.swapaxes(tok_rows, 1, 2)
            slots = np.broadcast_to(cmask[:, None, :], tok_bag.shape)
            np.testing.assert_array_equal(tok_bag[slots], tok_rows[slots])
            # the whole program: interpreted kernel vs reference vs host
            ok, geom = device_probe_plan(p, qs.shape[1], nprobe, 8,
                                         "device", t_cs=t_cs)
            if not ok:                  # a small last shard reads dense
                continue
            engaged += 1
            _, k, c_score, s_out = geom
            outs = [_device_candidates(
                cs, jnp.asarray(qm), div.doc_member,
                jnp.ones(p.n_docs, bool), k=k, t_cs=t_cs, ndocs=8,
                c_score=c_score, s_out=s_out, impl=impl)
                for impl in ("ref", "kernel")]
            for ref, got in zip(outs[0], outs[1]):
                np.testing.assert_array_equal(np.asarray(ref),
                                              np.asarray(got))
            host = plaid_candidates(p, qs, nprobe=nprobe, t_cs=t_cs,
                                    ndocs=8, q_mask=qm,
                                    probe_kernel="host")
            assert survivors(*host) == survivors(*outs[1])
    assert engaged, "the device candidate path never engaged"


@pytest.fixture(scope="module")
def many_small_docs():
    rng = np.random.default_rng(61)
    idx = MultiVectorIndex(dim=DIM, backend="plaid", doc_maxlen=24,
                           n_centroids=128, ndocs=16)
    idx.add(unit_docs(rng, n=2000, lo=1, hi=3))
    return idx._plaid


@pytest.mark.parametrize("case,want", [
    ("t_cs=0.3", None), ("t_cs=0", None), ("narrow_slots", None),
    ("t_cs<0", "negative_t_cs"), ("long_doc", "long_docs")])
def test_probe_plan_falls_back_where_the_bag_is_inexact(many_small_docs,
                                                        case, want):
    """The device path (bag scorer) engages for ``t_cs >= 0`` at any
    slot width, however small against the corpus; the host path serves
    a negative ``t_cs`` (a 0 could beat a pruned score) and an index
    with a doc longer than ``doc_maxlen`` (cut in the code view, not in
    the bag), and says why."""
    p = many_small_docs
    lq, nprobe, t_cs = 32, 4, 0.3
    if case == "t_cs=0":
        t_cs = 0.0
    elif case == "narrow_slots":
        lq, nprobe = 1, 1
    elif case == "t_cs<0":
        t_cs = -0.1
    elif case == "long_doc":        # docs of 4-19 tokens, doc_maxlen 8
        p = build(np.random.default_rng(67), n=40, doc_maxlen=8)._plaid
    ok, geom = device_probe_plan(p, lq, nprobe, 16, "device", t_cs=t_cs)
    qs = unit_queries(np.random.default_rng(71), 2, lq=lq)
    cand, _ = plaid_candidates(p, qs, nprobe=nprobe, t_cs=t_cs, ndocs=16,
                               probe_kernel="device")
    if want is None:
        assert ok, geom
        if case == "narrow_slots":
            assert 16 * geom[2] < p.n_docs
        assert isinstance(cand, jax.Array)
    else:
        assert (ok, geom) == (False, want)
        assert isinstance(cand, np.ndarray)


# --------------------------------------------------------- property sweep
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                         # pragma: no cover
    HAVE_HYP = False


if HAVE_HYP:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 10 ** 6),
           nprobe=st.integers(1, 6),
           t_cs=st.sampled_from([0.0, 0.3, 0.9]),
           ndocs=st.sampled_from([4, 16, 4096]),
           n_docs=st.integers(6, 60),
           mask=st.sampled_from(["none", "partial", "fullrow"]),
           deletes=st.booleans())
    def test_device_equals_host_property(seed, nprobe, t_cs, ndocs,
                                         n_docs, mask, deletes):
        rng = np.random.default_rng(seed)
        idx = build(rng, n=n_docs, ndocs=ndocs, nprobe=nprobe, t_cs=t_cs)
        if deletes and n_docs > 4:
            idx.delete(list(rng.choice(n_docs, size=2, replace=False)))
        qs = unit_queries(rng, 3)
        qm = None
        if mask != "none":
            qm = np.asarray(rng.random(qs.shape[:2]) > 0.3)
            qm[0, 0] = True                 # keep row 0 probing
            if mask == "fullrow":
                qm[1] = False
        assert_candidates_equal(idx, qs, q_mask=qm)
else:                                       # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_device_equals_host_property():
        pass
