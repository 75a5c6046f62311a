"""Mosaic compiles of the serving/indexing kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached. Each test lowers one Pallas kernel at the
widths the full-size ColBERTv2 path uses (dim 128, Lq 32, K 256, pooled
docs of 136 tokens, Ward over N = 256, the bag probe over 32768 docs)
with ``interpret=False`` and asserts the compiled program holds the
kernel (``tpu_custom_call``); the long-doc encoder's smoke model is
compiled whole, through the masked kernel.
Mosaic rejects what interpret mode accepts — block shapes off the
(8, 128) tiling, unsupported reshapes, more VMEM than the scoped
limit — so these keep the chip path compiling without chip time.

The topology is described inside a module fixture (never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.maxsim.kernel import maxsim_pallas, maxsim_rerank_pallas
from repro.kernels.maxsim_packed.kernel import maxsim_packed_rerank_pallas
from repro.kernels.plaid_probe.kernel import plaid_probe_bag_pallas
from repro.kernels.flash_attention.masked import masked_attention
from repro.kernels.ward_pool.kernel import (ward_pool_pallas,
                                            ward_pool_rows_pallas)

NQ, LQ, DIM, K, LD, S = 8, 32, 128, 256, 136, 256
I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_ward_pool_compiles(one_chip):
    """N = doc_maxlen = 256: the [8, 256, 256] f32 distance block (2 MiB)
    plus the merge loop's temporaries must fit the scoped VMEM."""
    B, N = 16, 256
    _compile(lambda *a: ward_pool_pallas(*a, block_b=8), one_chip,
             ((B, N, N), F32), ((B, 1, N), I32), ((B, 1, 1), I32),
             ((B // 8,), I32))


def test_ward_pool_long_docs_compiles(one_chip):
    """N = 2048: one doc's [2048, 2048] f32 matrix (16 MiB) resident in
    VMEM as 16 column stripes of 128 lanes, rows read and written at a
    dynamic sublane stride, within the kernel's VMEM limit."""
    B, N = 16, 2048
    _compile(ward_pool_rows_pallas, one_chip,
             ((B, N, N), F32), ((B, N // 128, 128), I32), ((B, 1, 1), I32),
             ((B,), I32))


@pytest.mark.parametrize("window", [0, 64])
def test_masked_attention_compiles(one_chip, window):
    """The long-doc encoder's attention at (16, 2048), 12 heads of 64:
    global (window 0) and banded (64); no [16, 12, 2048, 2048] f32 score
    buffer in the program."""
    B, S, H, dh = 16, 2048, 12, 64
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (((B, S, H, dh), bf),) * 3 + (((B, S), jnp.bool_),)]
    text = jax.jit(lambda q, k, v, m: masked_attention(
        q, k, v, m, window=window)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"f32[{B},{H},{S},{S}]" not in text


def test_long_doc_encoder_compiles_with_its_scopes(one_chip, monkeypatch):
    """``encode_docs`` of the ModernBERT smoke model, past its threshold,
    takes the masked kernel on the chip, and its ops keep the scopes the
    benchmark's attention metrics read."""
    import dataclasses
    import re
    from repro.configs.moderncolbert import SMOKE
    from repro.models import attention
    from repro.models.colbert import encode_docs, init_colbert
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    # a config of its own name: encode_docs traces afresh
    cfg = dataclasses.replace(SMOKE, name="smoke-tpu-compile")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_colbert(jax.random.PRNGKey(0), cfg)))
    toks = jax.ShapeDtypeStruct((2, cfg.doc_maxlen - 2), I32,
                                sharding=one_chip)
    text = encode_docs.lower(params, toks, cfg).compile().as_text()
    assert "tpu_custom_call" in text
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attention", "attention/global", "attention/local", "mlp"):
        assert any(re.search(rf"encoder/(.*/)?{scope}/", n)
                   for n in names), scope


@pytest.mark.parametrize("scan", ["all_docs", "rerank"])
def test_maxsim_compiles(one_chip, scan):
    if scan == "all_docs":
        _compile(maxsim_pallas, one_chip, ((NQ, LQ, DIM), F32),
                 ((NQ, LQ, 1), I32), ((4 * S, LD, DIM), F32),
                 ((4 * S, LD), I32))
    else:
        _compile(maxsim_rerank_pallas, one_chip, ((NQ, LQ, DIM), F32),
                 ((NQ, LQ, 1), I32), ((NQ, S, LD, DIM), F32),
                 ((NQ, S, LD), I32))


@pytest.mark.parametrize("bits", [2, 4])
def test_maxsim_packed_compiles(one_chip, bits):
    W = DIM * bits // 32
    _compile(lambda *a: maxsim_packed_rerank_pallas(*a, bits=bits),
             one_chip, ((NQ, LQ, DIM), F32), ((NQ, LQ, 1), I32),
             ((NQ, S, W, LD), I32), ((NQ, S, LD), I32), ((NQ, S, LD), I32),
             ((DIM, K), F32), ((DIM, 1 << bits), F32))


@pytest.mark.parametrize("nq,n_docs", [(32, 32768), (32, 32768 - 37),
                                        (8, 32768), (1, 32768)])
def test_plaid_probe_compiles(one_chip, nq, n_docs):
    """The serve cell's bag probe over K 256 and 32768 docs at the full
    batch of 32 queries x 32 tokens and at smaller batch buckets, and a
    ragged last doc tile."""
    _compile(plaid_probe_bag_pallas, one_chip, ((nq, LQ, K), F32),
             ((K, n_docs), F32))
