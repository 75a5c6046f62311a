"""jit'd public wrapper for the compressed-domain rerank kernel: pads the
candidate axis to the kernel's slot count (padded candidates are fully
masked), lays the operands out as the kernel expects, dispatches
(interpret=True off-TPU), unpads."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.maxsim.kernel import pad_slots
from repro.kernels.maxsim.ops import _on_tpu, _pad_axis_to, _q_mask_col
from repro.kernels.maxsim_packed.kernel import maxsim_packed_rerank_pallas


@functools.partial(jax.jit, static_argnames=("bits", "block_s"))
def maxsim_packed_rerank(q, q_mask, words, ids, d_mask, centroids, values,
                         *, bits: int = 2, block_s: int = 8):
    """Per-query candidate scores [Nq, S] straight from packed codes.

    words [Nq, S, Ld, W] packed residual words; ids [Nq, S, Ld] centroid
    ids; d_mask [Nq, S, Ld] token validity — the per-query gathers of the
    plaid packed views; centroids [K, dim] / values [dim, 2^bits] are the
    codec tables. Query i scores only its own slab words[i]. Its ops
    carry the scope ``rerank`` in the device trace."""
    S = words.shape[1]
    n = pad_slots(S, block_s)

    def pad(x):
        return _pad_axis_to(x, 1, n)

    with jax.named_scope("rerank"):
        words_t = jnp.swapaxes(jax.lax.bitcast_convert_type(
            pad(words.astype(jnp.uint32)), jnp.int32), 2, 3)
        out = maxsim_packed_rerank_pallas(
            jnp.asarray(q, jnp.float32), _q_mask_col(q_mask), words_t,
            pad(ids.astype(jnp.int32)), pad(d_mask).astype(jnp.int32),
            jnp.asarray(centroids, jnp.float32).T,
            jnp.asarray(values, jnp.float32),
            bits=bits, block_s=block_s, interpret=not _on_tpu())
        return out[:, 0, :S]
