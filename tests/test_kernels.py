"""Per-kernel shape/dtype sweeps vs the pure-jnp ref oracles
(interpret=True executes the kernel bodies on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.masked import masked_attention
from repro.kernels.kmeans_assign.ops import kmeans_assign
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
from repro.kernels.maxsim.ops import maxsim, maxsim_rerank
from repro.kernels.maxsim.ref import maxsim_ref, maxsim_rerank_ref
from repro.kernels.quant.ops import dequant_score
from repro.kernels.quant.ref import dequant_score_ref
from repro.models.attention import _full_attn


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


# ---------------------------------------------------------------- maxsim
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nq,lq,nd,ld,dim", [
    (3, 32, 9, 64, 128), (8, 16, 16, 128, 64), (1, 8, 5, 32, 128),
    (13, 32, 7, 256, 128),
])
def test_maxsim_sweep(nq, lq, nd, ld, dim, dtype):
    rng = np.random.default_rng(nq * ld)
    q = jnp.asarray(rng.normal(size=(nq, lq, dim)), dtype)
    d = jnp.asarray(rng.normal(size=(nd, ld, dim)), dtype)
    qm = jnp.asarray(rng.random((nq, lq)) > 0.2)
    dm = jnp.asarray(rng.random((nd, ld)) > 0.2)
    out = maxsim(q, qm, d, dm, block_d=4)
    ref = maxsim_ref(q, qm, d, dm)
    np.testing.assert_allclose(out, ref, rtol=tol(dtype), atol=tol(dtype)
                               * np.abs(np.asarray(ref)).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nq,lq,s,ld,dim", [
    (3, 32, 9, 64, 128), (8, 16, 5, 128, 64), (1, 8, 1, 32, 128),
])
def test_maxsim_rerank_sweep(nq, lq, s, ld, dim, dtype):
    """Gathered-candidate rerank: query i scores only its own slab d[i]."""
    rng = np.random.default_rng(nq * ld + s)
    q = jnp.asarray(rng.normal(size=(nq, lq, dim)), dtype)
    d = jnp.asarray(rng.normal(size=(nq, s, ld, dim)), dtype)
    qm = jnp.asarray(rng.random((nq, lq)) > 0.2)
    dm = jnp.asarray(rng.random((nq, s, ld)) > 0.2)
    out = maxsim_rerank(q, qm, d, dm, block_s=4)
    ref = maxsim_rerank_ref(q, qm, d, dm)
    np.testing.assert_allclose(out, ref, rtol=tol(dtype), atol=tol(dtype)
                               * np.abs(np.asarray(ref)).max())


def test_maxsim_all_docs_masked():
    q = jnp.ones((2, 4, 8), jnp.float32)
    d = jnp.ones((2, 4, 8), jnp.float32)
    qm = jnp.ones((2, 4), bool)
    dm = jnp.zeros((2, 4), bool)
    out = maxsim(q, qm, d, dm, block_d=2)
    assert np.allclose(np.asarray(out), 0.0)


# --------------------------------------------------------- kmeans_assign
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,k,dim", [(100, 8, 64), (257, 32, 128),
                                     (64, 5, 32)])
def test_kmeans_assign_sweep(n, k, dim, dtype):
    rng = np.random.default_rng(n + k)
    x = jnp.asarray(rng.normal(size=(n, dim)), dtype)
    c = jnp.asarray(rng.normal(size=(k, dim)), dtype)
    km = jnp.asarray(np.arange(k) < max(k - 2, 1))
    a, s = kmeans_assign(x, c, km, block_n=64)
    ar, sr = kmeans_assign_ref(x, c, km)
    assert (np.asarray(a) == np.asarray(ar)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=tol(dtype), atol=1e-2)


# ------------------------------------------------------------- quant
@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("m,dim,lq", [(100, 128, 16), (300, 64, 32)])
def test_dequant_score_sweep(m, dim, lq, bits):
    from repro.core.quantization import encode, train_codec
    rng = np.random.default_rng(m + bits)
    vecs = rng.normal(size=(m, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=-1, keepdims=True)
    cents = rng.normal(size=(16, dim)).astype(np.float32)
    cents /= np.linalg.norm(cents, axis=-1, keepdims=True)
    codec = train_codec(jnp.asarray(vecs), jnp.asarray(cents), bits=bits)
    ids, words = encode(codec, jnp.asarray(vecs))
    q = jnp.asarray(rng.normal(size=(lq, dim)), jnp.float32)
    out = dequant_score(words, ids, codec.centroids, codec.values, q,
                        bits=bits, block_m=64)
    rows = jnp.take(codec.centroids, ids, axis=0)
    ref = dequant_score_ref(words, rows, codec.values, q, bits=bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_pack_unpack_roundtrip():
    from repro.core.quantization import pack_codes, unpack_codes
    rng = np.random.default_rng(0)
    for bits in (2, 4, 8):
        codes = jnp.asarray(rng.integers(0, 1 << bits, (50, 128)), jnp.int32)
        words = pack_codes(codes, bits)
        back = unpack_codes(words, bits, 128)
        assert (np.asarray(back) == np.asarray(codes)).all()


# -------------------------------------------- masked attention (encoder)
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("b,h,s,dh", [
    (2, 4, 128, 64),
    (1, 8, 256, 128),
    (2, 4, 512, 64),
    (1, 4, 512, 64),
    (1, 2, 64, 128),
])
def test_masked_attention_sweep(b, h, s, dh, window):
    """The encoder's masked kernel, global and banded, against its dense
    path ``_full_attn``. Each batch gets one more row, fully padded,
    which comes out zero; row 0 is short and has a hole."""
    rng = np.random.default_rng(s + dh + window)
    B = b + 1
    q, k, v = (jnp.asarray(rng.normal(size=(B, s, h, dh)), jnp.bfloat16)
               for _ in range(3))
    mask = np.ones((B, s), bool)
    mask[0, s // 2:] = False
    mask[0, 3] = False
    mask[B - 1] = False
    got = np.asarray(masked_attention(q, k, v, jnp.asarray(mask),
                                      window=window, interpret=True),
                     np.float32)
    want = np.asarray(_full_attn(q, k, v, pad_mask=jnp.asarray(mask),
                                 window=window), np.float32)
    for i, n in enumerate([s // 2] + [s] * (b - 1)):   # valid query rows
        np.testing.assert_allclose(got[i, :n], want[i, :n],
                                   atol=2e-2, rtol=2e-2)
    assert not got[B - 1].any()
