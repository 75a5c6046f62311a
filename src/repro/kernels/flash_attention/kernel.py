"""FlashAttention-style online-softmax Pallas TPU kernel (fwd), GQA-aware.

Grid: (batch*q_heads, q blocks, kv blocks) with the kv axis innermost and
sequential ("arbitrary"); running max / denominator / accumulator live in
VMEM scratch and the output block is written once on the last kv step.

GQA: q is laid out [B*H, Sq, dh] and k/v [B*KV, Skv, dh]; the k/v BlockSpec
index maps program bh -> bh // group, so grouped query heads stream the
same kv tile (no materialized repeat).

Causal: kv blocks fully above the diagonal are skipped with pl.when (the
compute is masked AND the flops never issue — matches the exact-FLOPs
chunked reference in models/attention.py).

Block sizes default to (128, 512): q tile 128x128 f32 = 64 KiB, kv tile
512x128x2 = 256 KiB, scores 128x512 f32 = 256 KiB — comfortably inside
v5e VMEM with double-buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, scale: float, block_q: int, block_k: int,
                  q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: query block rows span [q_offset + iq*Bq, ... +Bq); kv block
    # cols span [ik*Bk, ... +Bk). Skip blocks entirely above the diagonal.
    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # [Bq, dh]
        k = k_ref[0].astype(jnp.float32)                # [Bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                             # [Bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    if causal:
        # skip kv blocks entirely above the causal diagonal
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 512,
                           interpret: bool = False):
    """q: [BH, Sq, dh]; k, v: [BKV, Skv, dh]; BH % BKV == 0.

    Returns o [BH, Sq, dh]. Sq % block_q == 0, Skv % block_k == 0.
    For decode-style use (Sq < Skv) the causal diagonal is anchored
    bottom-right (q row i attends to kv cols <= Skv - Sq + i).
    """
    BH, Sq, dh = q.shape
    BKV, Skv, _ = k.shape
    assert BH % BKV == 0
    group = BH // BKV
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv)
    grid = (BH, Sq // block_q, Skv // block_k)
    scale = 1.0 / (dh ** 0.5)
    q_offset = Skv - Sq                    # causal anchor
    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
