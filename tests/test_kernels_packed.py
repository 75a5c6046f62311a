"""Fused compressed-domain rerank kernel vs the decode+maxsim oracle.

Sweeps bits x dim x token counts with hypothesis (real codecs trained on
random unit vectors, like test_kernels.test_dequant_score_sweep), pins
the BITWISE contract of the jnp reference path against the legacy
reconstruction composition (``quantization.decode`` -> jitted
``maxsim_rerank_ref``), and covers the degenerate edges (all-masked
rows, empty candidate slots, single-candidate slabs). interpret=True
executes the Pallas kernel body on CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

# only the shape sweep needs hypothesis (PR 1 convention: skip, don't
# fail, in containers without it); the deterministic parity/edge tests
# below run everywhere
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.quantization import decode, encode, train_codec
from repro.kernels.maxsim.ref import maxsim_rerank_ref
from repro.kernels.maxsim_packed.ops import maxsim_packed_rerank
from repro.kernels.maxsim_packed.ref import maxsim_packed_rerank_ref

_rerank_jnp = jax.jit(maxsim_rerank_ref)


def packed_case(seed, bits, dim, nq, s, ld, lq):
    """Train a real codec on random unit vectors and encode a slab grid."""
    rng = np.random.default_rng(seed)
    m = max(nq * s * ld, 64)
    vecs = rng.normal(size=(m, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=-1, keepdims=True)
    cents = rng.normal(size=(16, dim)).astype(np.float32)
    cents /= np.linalg.norm(cents, axis=-1, keepdims=True)
    codec = train_codec(jnp.asarray(vecs), jnp.asarray(cents), bits=bits)
    ids, words = encode(codec, jnp.asarray(vecs))
    n = nq * s * ld
    ids = jnp.asarray(np.asarray(ids)[:n].reshape(nq, s, ld))
    words = jnp.asarray(np.asarray(words)[:n].reshape(nq, s, ld, -1))
    dm = jnp.asarray(rng.random((nq, s, ld)) < 0.85)
    q = jnp.asarray(rng.normal(size=(nq, lq, dim)), jnp.float32)
    qm = jnp.asarray(rng.random((nq, lq)) < 0.9)
    return codec, q, qm, words, ids, dm


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10 ** 6), bits=st.sampled_from((2, 4)),
           dim=st.sampled_from((32, 64)), nq=st.integers(1, 3),
           s=st.integers(1, 9), ld=st.integers(1, 6), lq=st.integers(1, 5))
    def test_packed_kernel_matches_decode_ref(seed, bits, dim, nq, s,
                                              ld, lq):
        """Fused unpack+reconstruct+maxsim == decode-then-maxsim."""
        codec, q, qm, words, ids, dm = packed_case(seed, bits, dim, nq,
                                                   s, ld, lq)
        out = maxsim_packed_rerank(q, qm, words, ids, dm,
                                   codec.centroids, codec.values,
                                   bits=bits, block_s=4)
        ref = maxsim_packed_rerank_ref(q, qm, words, ids, dm,
                                       codec.centroids, codec.values,
                                       bits=bits)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4 * max(ld, 1))


@pytest.mark.parametrize("bits", [2, 4])
def test_packed_ref_bitwise_vs_reconstruction_path(bits):
    """The parity contract: the packed reference scores are BITWISE what
    the legacy path produces — eager ``quantization.decode`` into an f32
    slab, then the same jitted rerank oracle the CPU dispatcher uses."""
    codec, q, qm, words, ids, dm = packed_case(7, bits, 64, 2, 5, 4, 3)
    nq, s, ld, w = words.shape
    packed = maxsim_packed_rerank_ref(q, qm, words, ids, dm,
                                      codec.centroids, codec.values,
                                      bits=bits)
    d = decode(codec, ids.reshape(-1), words.reshape(-1, w))
    d = d.reshape(nq, s, ld, codec.dim)
    recon = _rerank_jnp(q, qm, d, dm)
    assert np.array_equal(
        np.asarray(packed).view(np.int32),
        np.asarray(recon).view(np.int32)), "packed scores drifted bitwise"


def test_packed_kernel_all_masked_rows():
    """Fully masked doc tokens score 0 (like the recon path), never NaN."""
    codec, q, qm, words, ids, dm = packed_case(3, 2, 32, 2, 4, 3, 2)
    dm = jnp.zeros_like(dm)
    out = maxsim_packed_rerank(q, qm, words, ids, dm,
                               codec.centroids, codec.values, bits=2)
    assert np.array_equal(np.asarray(out), np.zeros(out.shape, np.float32))
    qm0 = jnp.zeros_like(qm)
    _, _, _, _, _, dm_live = packed_case(3, 2, 32, 2, 4, 3, 2)
    out = maxsim_packed_rerank(q, qm0, words, ids, dm_live,
                               codec.centroids, codec.values, bits=2)
    assert np.array_equal(np.asarray(out), np.zeros(out.shape, np.float32))


def test_packed_kernel_single_candidate():
    """S=1 (below block_s: the wrapper pads the slab axis)."""
    codec, q, qm, words, ids, dm = packed_case(11, 4, 32, 1, 1, 2, 2)
    out = maxsim_packed_rerank(q, qm, words, ids, dm,
                               codec.centroids, codec.values,
                               bits=4, block_s=8)
    ref = maxsim_packed_rerank_ref(q, qm, words, ids, dm,
                                   codec.centroids, codec.values, bits=4)
    assert out.shape == (1, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_packed_store_empty_candidate_rows():
    """A query whose candidate mask is all-False comes back -inf across
    the row (the store-level contract the top-k fetch turns into -1 ids)."""
    from repro.core.index import MultiVectorIndex
    from repro.core.plaid import maxsim_packed_rerank_store
    rng = np.random.default_rng(0)
    docs = [rng.normal(size=(6, 32)).astype(np.float32) for _ in range(8)]
    idx = MultiVectorIndex(dim=32, backend="plaid", n_centroids=8,
                           doc_maxlen=16)
    idx.add(docs)
    q = jnp.asarray(rng.normal(size=(2, 3, 32)), jnp.float32)
    qm = jnp.ones((2, 3), bool)
    cand = np.zeros((2, 4), np.int64)
    cmask = np.array([[True, True, False, False],
                      [False, False, False, False]])
    s = maxsim_packed_rerank_store(idx._plaid, q, qm, cand, cmask)
    s = np.asarray(s)
    assert np.isfinite(s[0, :2]).all()
    assert (s[0, 2:] == -np.inf).all() and (s[1] == -np.inf).all()
