"""Fused compressed-domain MaxSim rerank Pallas TPU kernel.

The PLAID stage-4 rerank without the f32 reconstruction store: each
program streams one candidate slab's PACKED residual words + centroid
ids into VMEM, reconstructs the token vectors in-register
(``kernels/quant.unpack_reconstruct_t``), and runs the masked
max-over-doc-tokens / sum-over-query-tokens reduction in the same pass.
HBM traffic per candidate token drops from ``dim*4`` reconstruction
bytes to ``4 + W*4`` code bytes (~14x at dim=128, b=2) while the MXU
work is unchanged (see ``repro.roofline.packed``).

Each candidate is reconstructed transposed, ``[dim, Ld]`` (tokens on
lanes), because its ids and words arrive as lane rows. The centroid-row
gather is a one-hot MXU matmul ``centroids^T [dim, K] x onehot [K, Ld]``
at HIGHEST precision: every one-hot column selects exactly one f32
centroid row, and at the TPU's default precision that row would be
rounded to bf16. K is small (<= 256), so the extra matmul costs a few
percent of the scoring matmul. The [dim, K] table and the [dim, 2^b]
value plane stay VMEM-resident across the whole grid.

Grid, layout and output tiling are ``kernels/maxsim``'s: one program
per (query, slab of ``block_s`` candidates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.maxsim.kernel import (compiler_params, maxsim_from_sim,
                                         pad_slots, place, slab_out_spec)
from repro.kernels.quant.kernel import unpack_reconstruct_t


def _maxsim_packed_rerank_kernel(q_ref, qm_ref, w_ref, id_ref, dm_ref,
                                 ct_ref, v_ref, o_ref, *, bits: int):
    """One query x one slab of its own candidates, scored from codes:
    q [Lq, dim], qm [Lq, 1], words [block, W, Ld], ids/dm [block, Ld]."""
    q = q_ref[...].astype(jnp.float32)
    qm = qm_ref[...] != 0
    ids = id_ref[...]
    dm = dm_ref[...] != 0
    centroids_t = ct_ref[...]
    K, Ld = centroids_t.shape[1], ids.shape[1]
    scores = []
    for b in range(ids.shape[0]):
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (K, Ld), 0)
                  == ids[b:b + 1]).astype(jnp.float32)
        rows_t = jax.lax.dot_general(
            centroids_t, onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                 # [dim, Ld]
        d_t = unpack_reconstruct_t(w_ref[b], rows_t, v_ref[...],
                                   bits=bits)
        sim = jax.lax.dot_general(q, d_t, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        scores.append(maxsim_from_sim(sim, qm, dm[b:b + 1]))
    o_ref[...] = place(o_ref[...], scores, pl.program_id(1), ids.shape[0])


@functools.partial(jax.jit, static_argnames=("bits", "block_s", "interpret"))
def maxsim_packed_rerank_pallas(q, q_mask, words_t, ids, d_mask,
                                centroids_t, values, *, bits: int = 2,
                                block_s: int = 8, interpret: bool = False):
    """q [Nq, Lq, dim]; q_mask [Nq, Lq, 1] int32; words_t [Nq, S, W, Ld]
    int32 packed codes (token axis last); ids [Nq, S, Ld] int32 centroid
    ids; d_mask [Nq, S, Ld] int32; centroids_t [dim, K]; values
    [dim, 2^bits] -> scores [Nq, 1, S] f32.
    S == ``pad_slots(S, block_s)`` (the wrapper pads)."""
    Nq, Lq, dim = q.shape
    _, S, W, Ld = words_t.shape
    K = centroids_t.shape[1]
    assert S == pad_slots(S, block_s), (S, block_s)
    kernel = functools.partial(_maxsim_packed_rerank_kernel, bits=bits)
    return pl.pallas_call(
        kernel,
        grid=(Nq, S // block_s),
        in_specs=[
            pl.BlockSpec((None, Lq, dim), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, Lq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_s, W, Ld), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, block_s, Ld), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_s, Ld), lambda i, j: (i, j, 0)),
            pl.BlockSpec((dim, K), lambda i, j: (0, 0)),
            pl.BlockSpec((dim, 1 << bits), lambda i, j: (0, 0)),
        ],
        out_specs=slab_out_spec(S, block_s),
        out_shape=jax.ShapeDtypeStruct((Nq, 1, S), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(q, q_mask, words_t, ids, d_mask, centroids_t, values)
