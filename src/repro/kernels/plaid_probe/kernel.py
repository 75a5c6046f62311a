"""Fused PLAID centroid-interaction Pallas TPU kernel (stages 1 + 3).

Candidate generation's matmul-shaped stages in one pass: each program
scores ONE query's tokens against the whole centroid table
(``q @ centroids^T`` on the MXU) and immediately runs the threshold-
pruned centroid-only MaxSim over one VMEM tile of its candidate code
rows — the approximate scores PLAID prunes with, straight from packed
centroid ids, without ever materializing the host path's
``[Nq, block, L, Lq]`` gathered-score intermediate in HBM.

The per-token centroid-score lookup is a one-hot MXU matmul, the same
gather-free idiom as ``kernels/maxsim_packed``: a candidate's code row
-> [K, L] select plane -> [Lq, L] pruned scores. Every column of the
select plane has exactly one 1.0 (ids live in [0, K)), so at HIGHEST
precision the contraction reproduces the reference's ``csT[code]``
gather exactly. The [dim, K] centroid table stays VMEM-resident across
the whole grid; per-candidate HBM traffic drops to the code bytes
(4B/token + mask) — see ``repro.roofline.probe``.

Grid, layout and output tiling are ``kernels/maxsim``'s: one program
per (query, tile of ``block_c`` candidates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.maxsim.kernel import (compiler_params, pad_slots, place,
                                         slab_out_spec)


def _plaid_probe_kernel(q_ref, qm_ref, ct_ref, code_ref, cm_ref, o_ref, *,
                        t_cs: float):
    """One query x one tile of its own candidates, scored centroid-only:
    q [Lq, dim], qm [Lq, 1], codes/cm [block, L] -> lanes of o [1, T]."""
    # stage 1: all centroid interactions for this query's tokens
    q = q_ref[...].astype(jnp.float32)                      # [Lq, dim]
    cs = jax.lax.dot_general(q, ct_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Lq, K]
    cs = jnp.where(qm_ref[...] != 0, cs, -jnp.inf)  # masked tokens add 0
    csp = jnp.where(cs >= t_cs, cs, 0.0)    # t_cs prune (-inf < t_cs)
    # stage 3: per-token centroid-score lookup as a one-hot MXU matmul,
    # at HIGHEST so the gathered f32 scores are exact on TPU too
    codes = code_ref[...]
    cm = cm_ref[...] != 0
    K, L = csp.shape[1], codes.shape[1]
    scores = []
    for b in range(codes.shape[0]):
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (K, L), 0)
                  == codes[b:b + 1]).astype(jnp.float32)
        vals = jax.lax.dot_general(csp, onehot, (((1,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        vals = jnp.where(cm[b:b + 1], vals, 0.0)            # [Lq, L]
        scores.append(jnp.sum(jnp.max(vals, axis=1, keepdims=True),
                              axis=0, keepdims=True))
    o_ref[...] = place(o_ref[...], scores, pl.program_id(1),
                       codes.shape[0])


@functools.partial(jax.jit,
                   static_argnames=("t_cs", "block_c", "interpret"))
def plaid_probe_pallas(q, q_mask, centroids_t, codes, code_mask, *,
                       t_cs: float, block_c: int = 8,
                       interpret: bool = False):
    """q [Nq, Lq, dim]; q_mask [Nq, Lq, 1] int32; centroids_t [dim, K];
    codes [Nq, C, L] int32 per-candidate centroid ids; code_mask
    [Nq, C, L] int32 -> approx scores [Nq, 1, C] f32 (the wrapper masks
    invalid candidate slots). C == ``pad_slots(C, block_c)``."""
    Nq, Lq, dim = q.shape
    _, C, L = codes.shape
    K = centroids_t.shape[1]
    assert C == pad_slots(C, block_c), (C, block_c)
    kernel = functools.partial(_plaid_probe_kernel, t_cs=t_cs)
    return pl.pallas_call(
        kernel,
        grid=(Nq, C // block_c),
        in_specs=[
            pl.BlockSpec((None, Lq, dim), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, Lq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((dim, K), lambda i, j: (0, 0)),
            pl.BlockSpec((None, block_c, L), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_c, L), lambda i, j: (i, j, 0)),
        ],
        out_specs=slab_out_spec(C, block_c),
        out_shape=jax.ShapeDtypeStruct((Nq, 1, C), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(q, q_mask, centroids_t, codes, code_mask)
