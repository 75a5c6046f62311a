"""Fused 2-bit dequantize + score Pallas TPU kernel.

PLAID stage-4 hot path: candidate token vectors live as packed residual
codes; the kernel unpacks (integer shifts on int32 words), reconstructs
(centroid row + bucket value), renormalizes, and scores against the query
block — all in VMEM, so the decompressed [M, dim] tensor never hits HBM.

The per-dimension bucket lookup values[dim, 2^b] is done WITHOUT a gather:
2-bit codes select among 4 broadcast value planes via a where-chain —
pure VPU selects, no scatter/gather unit involvement.

``unpack_reconstruct_t`` is THE in-tile packed-scoring primitive: both this
kernel and the fused compressed-domain maxsim rerank kernel
(kernels/maxsim_packed) build on it, and its arithmetic mirrors
``core.quantization.decode`` op for op (same normalize formula), so the
Pallas paths and the jnp reference paths reconstruct identical vectors
up to float evaluation order.

Tiling: grid over M blocks of tokens on lanes; values plane + query
block resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def unpack_reconstruct_t(words_t, rows_t, vals, *, bits: int):
    """In-tile unpack + reconstruct + renormalize (the shared primitive),
    transposed: dims on sublanes, tokens on lanes — the layout in which
    per-token codes arrive as lane rows.

    words_t: [W, L] int32 packed b-bit codes (bit pattern of the uint32
    words); rows_t: [dim, L] centroid rows; vals: [dim, 2^bits].
    Returns [dim, L] f32 unit-renormalized reconstructions. Dim p lives
    in word ``p // cpw`` at bit ``(p % cpw) * bits`` (little-endian, as
    ``pack_codes``); every word is broadcast to its cpw dims with a
    select and shifted per sublane — no reshape across the lane axis.
    """
    W, L = words_t.shape
    dim = rows_t.shape[0]
    cpw = 32 // bits
    p = jax.lax.broadcasted_iota(jnp.int32, (dim, 1), 0)
    word = jnp.zeros((dim, L), jnp.int32)
    for w in range(W):
        word = jnp.where(p // cpw == w, words_t[w:w + 1, :], word)
    codes = jax.lax.shift_right_logical(word, (p % cpw) * bits) & (
        (1 << bits) - 1)
    res = jnp.zeros((dim, L), jnp.float32)
    for b in range(1 << bits):
        res = jnp.where(codes == b, vals[:, b:b + 1], res)
    v = rows_t.astype(jnp.float32) + res
    nrm = jnp.sqrt(jnp.sum(v * v, axis=0, keepdims=True))
    return v / jnp.maximum(nrm, 1e-9)


def _dequant_score_kernel(w_ref, c_ref, v_ref, q_ref, o_ref, *, bits: int):
    v_t = unpack_reconstruct_t(w_ref[...], c_ref[...], v_ref[...],
                               bits=bits)                       # [dim, M]
    q = q_ref[...].astype(jnp.float32)                          # [Lq, dim]
    o_ref[...] = jax.lax.dot_general(q, v_t, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def dequant_score_pallas(words_t, rows_t, values, q, *, bits: int = 2,
                         block_m: int = 256, interpret: bool = False):
    """words_t [W, M] int32 packed codes; rows_t [dim, M] centroid rows;
    values [dim, 2^b]; q [Lq, dim] -> sims [Lq, M] f32.
    M % block_m == 0 (wrapper pads)."""
    W, M = words_t.shape
    dim = rows_t.shape[0]
    Lq = q.shape[0]
    assert M % block_m == 0
    kernel = functools.partial(_dequant_score_kernel, bits=bits)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((W, block_m), lambda i: (0, i)),
            pl.BlockSpec((dim, block_m), lambda i: (0, i)),
            pl.BlockSpec((dim, 1 << bits), lambda i: (0, 0)),
            pl.BlockSpec((Lq, dim), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Lq, block_m), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((Lq, M), jnp.float32),
        interpret=interpret,
    )(words_t, rows_t, values, q)
