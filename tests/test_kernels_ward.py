"""Ward-pooling Pallas kernel: bitwise parity with the reference loop.

The kernel runs in interpret mode on CPU (ops.py keys on the backend),
so the sweep here exercises the exact program CI and the TPU path
share. Three pins:

  * kernel assign == ``ward_cluster_batch`` BITWISE (not canonical-
    label equal — index artifacts must not depend on the impl),
  * both == SciPy's ward dendrogram cut (the existing fixture),
  * the pooled pipeline (``pool_doc_embeddings`` + ``compact_pooled``)
    is bitwise-identical through either impl, including the device-side
    compaction path.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # hypothesis gates only the sweep tests, not the fixed fixtures
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False
from scipy.cluster.hierarchy import fcluster, linkage

from repro.core.pooling import compact_pooled, pool_doc_embeddings
from repro.core.ward import ward_cluster_batch
from repro.kernels.ward_pool import ward_assign


def canon(labels):
    m, out = {}, []
    for v in labels:
        if v not in m:
            m[v] = len(m)
        out.append(m[v])
    return tuple(out)


def _assert_bitwise(x, mask, factor):
    ref = np.asarray(ward_cluster_batch(jnp.asarray(x), jnp.asarray(mask),
                                        factor))
    ker = np.asarray(ward_assign(jnp.asarray(x), jnp.asarray(mask),
                                 factor, impl="kernel"))
    np.testing.assert_array_equal(ref, ker)
    return ref


# ---------------------------------------------------------------------------
# hypothesis sweep: N x dim x factor x masked-gap patterns
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def test_kernel_matches_reference_bitwise(data):
        B = data.draw(st.integers(1, 12), label="B")
        N = data.draw(st.integers(2, 48), label="N")
        d = data.draw(st.integers(1, 40), label="d")
        factor = data.draw(st.integers(2, 6), label="factor")
        seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(B, N, d)).astype(np.float32)
        # masked-gap patterns: contiguous tails, interior holes, all-False
        mask = rng.random((B, N)) > data.draw(
            st.sampled_from([0.0, 0.25, 0.6, 1.0]), label="gap_p")
        if data.draw(st.booleans(), label="tail_gap"):
            mask[0, N // 2:] = False
        _assert_bitwise(x, mask, factor)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(2, 5))
    def test_tie_heavy_duplicates_match_bitwise(seed, factor):
        # duplicated rows force distance ties — merge ORDER must match
        rng = np.random.default_rng(seed)
        B, N, d = 3, 24, 8
        base = rng.normal(size=(B, N // 2, d)).astype(np.float32)
        x = np.concatenate([base, base], axis=1)
        x = x[:, rng.permutation(N)]
        mask = np.ones((B, N), bool)
        _assert_bitwise(x, mask, factor)

else:  # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_kernel_matches_reference_bitwise():
        pass


# ---------------------------------------------------------------------------
# the SciPy fixture, through the kernel path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("factor", [2, 3, 4, 6])
@pytest.mark.parametrize("seed", [0, 1])
def test_kernel_matches_scipy(factor, seed):
    rng = np.random.default_rng(seed)
    B, N, d = 4, 32, 16
    x = rng.normal(size=(B, N, d)).astype(np.float32)
    mask = np.ones((B, N), bool)
    mask[1, 25:] = False
    mask[3, 10:] = False
    assign = np.asarray(ward_assign(jnp.asarray(x), jnp.asarray(mask),
                                    factor, impl="kernel"))
    for b in range(B):
        xv = x[b][mask[b]]
        xv /= np.linalg.norm(xv, axis=-1, keepdims=True)
        k = xv.shape[0] // factor + 1
        sc = fcluster(linkage(xv, method="ward"), t=k, criterion="maxclust")
        assert canon(sc) == canon(assign[b][mask[b]]), (b, factor)


# ---------------------------------------------------------------------------
# edges: all-masked / single-token / n_valid <= factor / identicals
# ---------------------------------------------------------------------------
def test_edge_docs_match_bitwise():
    rng = np.random.default_rng(0)
    B, N, d = 4, 16, 8
    x = rng.normal(size=(B, N, d)).astype(np.float32)
    mask = np.ones((B, N), bool)
    mask[0, :] = False          # all-masked doc
    mask[1, 1:] = False         # single-token doc
    mask[2, 3:] = False         # n_valid (3) <= factor (4)
    for factor in (2, 4, 8):
        _assert_bitwise(x, mask, factor)


def test_identical_vectors_match_bitwise():
    # all pairwise distances zero: pure tie-break territory
    x = np.ones((2, 12, 4), np.float32)
    mask = np.ones((2, 12), bool)
    for factor in (2, 3):
        ref = _assert_bitwise(x, mask, factor)
        n_clusters = len(set(ref[0].tolist()))
        assert n_clusters == 12 // factor + 1


def test_impl_dispatch_and_validation():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 4)),
                    jnp.float32)
    mask = jnp.ones((2, 8), bool)
    a_auto = np.asarray(ward_assign(x, mask, 2, impl="auto"))
    a_ref = np.asarray(ward_assign(x, mask, 2, impl="ref"))
    np.testing.assert_array_equal(a_auto, a_ref)
    with pytest.raises(ValueError):
        ward_assign(x, mask, 2, impl="fused")


# ---------------------------------------------------------------------------
# docs per program from N, and the long-doc kernel, bitwise
# ---------------------------------------------------------------------------
def test_block_b_follows_n_and_the_budget():
    from repro.kernels.ward_pool.ops import resident, ward_block_b
    assert [ward_block_b(n) for n in (40, 256, 300, 512, 2048)] == \
        [8, 8, 5, 2, 1]
    assert resident(724) and not resident(725) and not resident(2048)


@pytest.mark.parametrize("N,kw", [(40, dict(block_b=1)),
                                  (40, dict(block_b=3)),
                                  (40, dict(lanes=8)),
                                  (64, dict(lanes=16)),
                                  (50, dict(lanes=16))])  # N padded to 64
@pytest.mark.parametrize("factor", [2, 3])
def test_kernels_bitwise_at_each_block(N, kw, factor):
    """The resident kernel at 1 and 3 docs a program and the long-doc
    kernel (one doc a program, column stripes of 8 or 16 lanes, so rows
    i and j fall in different stripes) assign bitwise like
    ``ward_cluster_batch``: ties, short, holed and empty docs."""
    rng = np.random.default_rng(N + factor)
    B, d = 5, 16
    x = rng.normal(size=(B, N, d)).astype(np.float32)
    x[:, N // 2] = x[:, 3]                        # exact ties
    mask = np.ones((B, N), bool)
    mask[1, N // 2:] = False
    mask[2, ::3] = False
    mask[3] = False
    x, mask = jnp.asarray(x), jnp.asarray(mask)
    want = np.asarray(ward_cluster_batch(x, mask, factor))
    got = np.asarray(ward_assign(x, mask, factor, impl="kernel", **kw))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the long-doc kernel: row minima kept by rules, not by walking the matrix
# ---------------------------------------------------------------------------
@jax.jit
def _lance_williams(si, sj, sc, d2i, d2j, dij):
    """The reference's expression, compiled as XLA compiles it (NumPy
    rounds its products and sums differently)."""
    return ((si + sc) * d2i + (sj + sc) * d2j
            - sc * dij) / jnp.maximum(si + sj + sc, 1e-9)


def _rescan_model(d2, mask, factor):
    """NumPy model of one doc's merges under the long-doc kernel's
    row-minimum rules -> (assign, rescans). It asserts after every merge
    that the kept minima and first columns are the matrix's own, and
    that the merge picked is the reference's flat argmin."""
    d2, N = d2.copy(), len(d2)
    sizes = mask.astype(np.float32)
    assign = np.arange(N, dtype=np.int32)
    n_valid = int(mask.sum())
    k = max(n_valid // factor + 1, 1)
    rmin, rarg = d2.min(1), d2.argmin(1).astype(np.int32)
    rescans = 0
    for _ in range(max(n_valid - k, 0)):
        i0 = int(np.argmin(rmin))
        i, j = sorted((i0, int(rarg[i0])))
        assert (i0, rarg[i0]) == divmod(int(np.argmin(d2)), N)
        dij = rmin[i0]
        if not np.isfinite(dij):
            continue
        si, sj, sc = sizes[i], sizes[j], sizes
        new = np.array(_lance_williams(si, sj, sc, d2[i], d2[j], dij))
        new[np.isinf(d2[i]) | np.isinf(d2[j])] = np.inf
        new[[i, j]] = np.inf
        d2[i], d2[:, i], d2[j], d2[:, j] = new, new, np.inf, np.inf
        sizes[i], sizes[j] = si + sj, 0.0
        assign[assign == j] = i
        hit = (rarg == i) | (rarg == j)
        lt, eq = new < rmin, new == rmin
        redo = np.flatnonzero(hit & (new > rmin))
        redo = redo[(redo != i) & (redo != j)]
        rarg = np.where(lt | (eq & (hit | (i < rarg))), i, rarg)
        rmin = np.where(lt, new, rmin)
        rmin[i], rarg[i] = new.min(), new.argmin()
        rmin[j], rarg[j] = np.inf, 0
        for r in redo:
            rmin[r], rarg[r] = d2[r].min(), d2[r].argmin()
        rescans += len(redo)
        np.testing.assert_array_equal(rmin, d2.min(1))
        np.testing.assert_array_equal(rarg, d2.argmin(1))
    return assign, rescans


def _docs(B, N, d, seed, groups=0):
    """Seeded docs: random vectors, or (``groups``) that many distinct
    vectors each repeated, so most distances tie; with a short, a holed
    and an empty doc."""
    rng = np.random.default_rng(seed)
    if groups:
        base = rng.normal(size=(B, groups, d)).astype(np.float32)
        x = base[:, rng.integers(0, groups, size=N)]
    else:
        x = rng.normal(size=(B, N, d)).astype(np.float32)
    mask = np.ones((B, N), bool)
    mask[1, N // 2:] = False
    mask[2, ::3] = False
    mask[3] = False
    return jnp.asarray(x), jnp.asarray(mask)


@pytest.mark.parametrize("groups", [3, 7])
@pytest.mark.parametrize("factor", [2, 4])
def test_long_doc_kernel_ties_bitwise(groups, factor):
    """Groups of identical vectors: zero distances and equal
    Lance-Williams values everywhere, so the tie rules (``v == rmin``,
    ``i < rarg``) and rescans at equal values decide the merge order."""
    from repro.kernels.ward_pool.ops import ward_rescans
    x, mask = _docs(5, 48, 8, groups + factor, groups=groups)
    want = np.asarray(ward_cluster_batch(x, mask, factor))
    got, rescans = ward_rescans(x, mask, factor, lanes=8)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert np.asarray(rescans).sum() > 0


@pytest.mark.parametrize("groups", [0, 5])
@pytest.mark.parametrize("factor", [2, 3])
def test_long_doc_rescans_match_numpy_model(groups, factor):
    """The kernel's per-doc rescan count equals the NumPy model's, and
    both assign like the reference."""
    from repro.core.ward import ward_distances
    from repro.kernels.ward_pool.ops import ward_rescans
    x, mask = _docs(5, 40, 16, 11 * factor + groups, groups=groups)
    got, rescans = ward_rescans(x, mask, factor, lanes=8)
    want = np.asarray(ward_cluster_batch(x, mask, factor))
    np.testing.assert_array_equal(np.asarray(got), want)
    # compiled, as the kernel's wrapper and the reference compute them:
    # run op by op, the matmul can round near-zero distances otherwise
    d2 = np.asarray(jax.jit(jax.vmap(ward_distances))(x, mask))
    model = [_rescan_model(d2[b], np.asarray(mask[b]), factor)
             for b in range(len(d2))]
    np.testing.assert_array_equal(np.stack([a for a, _ in model]), want)
    assert np.asarray(rescans).tolist() == [n for _, n in model]


def _merge_loop_ref(d2, mask, k, steps):
    """``core/ward.py``'s merge step, run ``steps`` times from given
    distances."""
    from repro.core.ward import _merge_once

    def one(d2, mask, k):
        state = (d2, jnp.where(mask, 1.0, 0.0), jnp.arange(len(mask)),
                 jnp.sum(mask.astype(jnp.int32)))
        body = lambda _, s: _merge_once(*s, k)
        return jax.lax.fori_loop(0, steps, body, state)[2]

    return np.asarray(jax.vmap(one)(d2, mask, k))


def test_long_doc_kernel_when_finite_pairs_run_out():
    """Two groups of tokens with no finite distance between them and a
    target of one cluster: the merges run out of finite pairs before
    the budget, and the rest must be no-ops, like the reference's."""
    from repro.core.ward import ward_distances
    from repro.kernels.ward_pool.kernel import ward_pool_rows_pallas
    x, mask = _docs(4, 32, 8, 3)
    x, mask = x[:2], jnp.ones_like(mask[:2])
    d2 = jax.vmap(ward_distances)(x, mask)
    group = np.arange(32) % 3 == 0
    apart = group[:, None] != group[None, :]
    d2 = jnp.where(apart, jnp.inf, d2)
    steps = np.int32(31)
    k = jnp.ones((2,), jnp.int32)
    want = _merge_loop_ref(d2, mask, k, steps)
    assert len(set(want[0].tolist())) == 2          # two clusters left
    got, rescans = ward_pool_rows_pallas(
        d2, mask.astype(jnp.int32).reshape(2, 4, 8), k[:, None, None],
        jnp.full((2,), steps), lanes=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got).reshape(2, 32), want)


# ---------------------------------------------------------------------------
# the full pooled pipeline through either impl, incl. device compaction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("factor", [2, 3])
def test_pooled_pipeline_bitwise_identical(factor):
    rng = np.random.default_rng(5)
    B, N, d = 7, 40, 16          # B deliberately not a block_b multiple
    x = jnp.asarray(rng.normal(size=(B, N, d)), jnp.float32)
    mask = np.ones((B, N), bool)
    mask[2, 30:] = False
    mask[5, :] = False
    mask = jnp.asarray(mask)
    pk, mk = pool_doc_embeddings(x, mask, factor, "ward",
                                 ward_kernel="kernel")
    pr, mr = pool_doc_embeddings(x, mask, factor, "ward",
                                 ward_kernel="ref")
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    # device-side compaction == host boolean gather, bitwise
    dev = compact_pooled(pk, mk)
    host = compact_pooled(np.asarray(pk), np.asarray(mk))
    assert len(dev) == len(host) == B
    for a, b in zip(dev, host):
        np.testing.assert_array_equal(a, b)
