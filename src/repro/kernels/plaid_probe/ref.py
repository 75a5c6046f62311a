"""Pure-jnp oracle for the doc-major probe kernel, and the summation
order it shares with the host path.

``plaid_probe_bag_ref`` takes a running max over centroids, then sums
the query tokens in ``fold_sum``'s order — the order the kernel and the
host path's stage 3 (``core.plaid._approx_scores_batch``) use too, so
all three agree bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fold_sum(x, axis: int):
    """Sum over ``axis`` (keeping it, size 1) by repeated halving: one
    fixed order of f32 additions for every caller, where an XLA reduce
    may order them by shape and backend."""
    n = x.shape[axis]
    while n > 1:
        h = n // 2
        part = (jax.lax.slice_in_dim(x, 0, h, axis=axis)
                + jax.lax.slice_in_dim(x, h, 2 * h, axis=axis))
        if n % 2:
            part = jnp.concatenate(
                [part, jax.lax.slice_in_dim(x, 2 * h, n, axis=axis)],
                axis=axis)
        x, n = part, part.shape[axis]
    return x


@jax.jit
def plaid_probe_bag_ref(csp, doc_member):
    """Same contract as ``kernel.plaid_probe_bag_pallas``: csp [Nq, Lq, K]
    (>= 0), doc_member [K, n_docs] 0/1 -> [Nq, n_docs] f32."""
    Nq, Lq, K = csp.shape

    def step(k, acc):
        return jnp.maximum(acc, csp[:, :, k, None] * doc_member[k])

    acc = jax.lax.fori_loop(
        0, K, step, jnp.zeros((Nq, Lq, doc_member.shape[1]), jnp.float32))
    return fold_sum(acc, 1)[:, 0]
