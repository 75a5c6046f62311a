"""Key-padding-masked bidirectional attention, global or banded: the
long-document encoder's attention (ModernBERT's layers at 2048 tokens).

One program holds one (doc, head): q, k, v [S, dh] and the output stay
in VMEM, and loops inside the program walk the query blocks, so no
score matrix larger than a block ever exists and a grid step's fixed
cost is paid once per head, not once per block.

* global (``window == 0``): each query block runs an online softmax over
  the key blocks up to the doc's last valid key; key blocks past it are
  not computed.
* banded (``window > 0``): query i attends keys with |i - j| <= window.
  A query block of ``BQ`` rows reads one span of ``BQ + 2 * rw`` keys
  (``rw`` = window rounded up to the 16-row bf16 tile) that holds its
  whole band; every key outside the span is outside the band, and the
  band mask inside the span is exact.

Key validity comes in per block (``[B, n_blocks, 1, width]``, gathered
by the wrapper at static offsets), so any pad mask is exact, holes
included. Query blocks past the doc's last valid token are not computed
and come out zero: they are padding, and no valid query attends them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
ALIGN = 16                  # rows of a bf16 tile: span starts align to it
GLOBAL_BQ, GLOBAL_BK = 256, 512
LOCAL_BQ = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def band_span(S: int, window: int, bq: int = LOCAL_BQ) -> tuple:
    """(span width, [start of each query block's key span]) of the banded
    path over ``S`` (a multiple of ``bq``) tokens."""
    rw = _round_up(window, ALIGN)
    width = min(bq + 2 * rw, S)
    starts = [min(max(q0 - rw, 0), S - width) for q0 in range(0, S, bq)]
    return width, starts


def _global_kernel(lens_ref, q_ref, k_ref, v_ref, kv_ref, o_ref, *, heads,
                   scale, bq, bk):
    n = lens_ref[pl.program_id(0) // heads]
    o_ref[...] = jnp.zeros_like(o_ref)
    dh = q_ref.shape[-1]

    def q_block(iq, _):
        q0 = pl.multiple_of(iq * bq, bq)
        q = q_ref[0, pl.ds(q0, bq), :]

        def kv_block(ik, carry):
            m, l, acc = carry
            k0 = pl.multiple_of(ik * bk, bk)
            s = jax.lax.dot_general(
                q, k_ref[0, pl.ds(k0, bk), :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(kv_ref[0, ik] > 0, s, NEG)            # [bq, bk]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(s > NEG, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, pl.ds(k0, bk), :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return (m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True),
                    acc * alpha + pv)

        init = (jnp.full((bq, 1), NEG, jnp.float32),
                jnp.zeros((bq, 1), jnp.float32),
                jnp.zeros((bq, dh), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (n + bk - 1) // bk, kv_block, init)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, pl.ds(q0, bq), :] = (acc / l).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, (n + bq - 1) // bq, q_block, 0)


def _band_kernel(lens_ref, q_ref, k_ref, v_ref, kv_ref, o_ref, *, heads,
                 scale, bq, window, width, rw):
    n = lens_ref[pl.program_id(0) // heads]
    o_ref[...] = jnp.zeros_like(o_ref)
    S = q_ref.shape[1]

    def q_block(iq, _):
        q0 = pl.multiple_of(iq * bq, bq)
        k0 = pl.multiple_of(jnp.clip(q0 - rw, 0, S - width), ALIGN)
        s = jax.lax.dot_general(
            q_ref[0, pl.ds(q0, bq), :], k_ref[0, pl.ds(k0, width), :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [bq, width]
        rows = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (jnp.abs(rows - cols) <= window) & (kv_ref[0, iq] > 0)
        s = jnp.where(keep, s, NEG)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(keep, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, pl.ds(k0, width), :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[0, pl.ds(q0, bq), :] = (
            pv / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, (n + bq - 1) // bq, q_block, 0)


@functools.partial(jax.jit, static_argnames=("heads", "window", "interpret"))
def masked_attention_pallas(q, k, v, kv_blocks, lens, *, heads: int,
                            window: int = 0, interpret: bool = False):
    """q, k, v [B * heads, S, dh] (S a multiple of the path's blocks);
    kv_blocks [B, n_blocks, 1, width] f32 key validity of each block (the
    global path's key blocks, or the banded path's query-block spans);
    lens [B] int32 last valid token + 1. Returns o [B * heads, S, dh]."""
    BH, S, dh = q.shape
    scale = 1.0 / float(np.sqrt(dh))
    if window:
        width, _ = band_span(S, window)
        kernel = functools.partial(
            _band_kernel, heads=heads, scale=scale, bq=LOCAL_BQ,
            window=window, width=width, rw=_round_up(window, ALIGN))
    else:
        bq, bk = (S, S) if S <= GLOBAL_BK else (GLOBAL_BQ, GLOBAL_BK)
        kernel = functools.partial(_global_kernel, heads=heads, scale=scale,
                                   bq=bq, bk=bk)
    whole = pl.BlockSpec((1, S, dh), lambda i, lens: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH,),
            in_specs=[whole, whole, whole,
                      pl.BlockSpec((1,) + kv_blocks.shape[1:],
                                   lambda i, lens: (i // heads, 0, 0, 0))],
            out_specs=whole),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lens, q, k, v, kv_blocks)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def masked_attention(q, k, v, pad_mask, *, window: int = 0,
                     interpret: bool = False):
    """q, k, v [B, S, H, dh]; pad_mask [B, S] True = valid key. Returns
    [B, S, H, dh]. Rows of a query block wholly past a doc's last valid
    token are zero; other padded rows hold whatever their keys give."""
    B, S, H, dh = q.shape
    Sp = _round_up(S, LOCAL_BQ if window or S <= GLOBAL_BK else GLOBAL_BK)
    valid = jnp.pad(pad_mask, ((0, 0), (0, Sp - S))).astype(jnp.float32)
    lens = jnp.max(jnp.where(valid > 0, jnp.arange(Sp) + 1, 0),
                   axis=1).astype(jnp.int32)
    if window:
        width, starts = band_span(Sp, window)
        kv_blocks = jnp.stack([valid[:, s:s + width] for s in starts], 1)
    else:
        bk = min(GLOBAL_BK, Sp)
        kv_blocks = valid.reshape(B, Sp // bk, bk)
    kv_blocks = kv_blocks[:, :, None, :]

    def heads_major(x):
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(B * H, Sp, dh)

    o = masked_attention_pallas(heads_major(q), heads_major(k),
                                heads_major(v), kv_blocks, lens, heads=H,
                                window=window, interpret=interpret)
    return o.reshape(B, H, Sp, dh).transpose(0, 2, 1, 3)[:, :S]
