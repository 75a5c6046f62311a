"""jit'd public wrapper for the Ward-pooling kernel: computes the
reference's initial distances, pads the doc batch to a block multiple
with fully-masked docs, dispatches to the Pallas kernel (interpret=True
off-TPU), and unpads.

``impl`` resolution (what ``PoolingSpec.ward_kernel`` carries):
  * ``"auto"``   — the kernel on TPU, ``core/ward.py`` elsewhere (the
    two are bitwise-equal; under the CPU interpreter the kernel is the
    slower of the two, so it is a correctness tool there).
  * ``"kernel"`` — force the Pallas path.
  * ``"ref"``    — force ``core/ward.py``'s ``ward_cluster_batch``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.ward import ward_distances
from repro.kernels.maxsim.ops import _on_tpu, _pad_to
from repro.kernels.ward_pool.kernel import ward_pool_pallas
from repro.kernels.ward_pool.ref import ward_assign_ref

WARD_IMPLS = ("auto", "kernel", "ref")


def resolve_impl(impl: str) -> str:
    """'auto'|'kernel'|'ref' -> 'kernel'|'ref'."""
    if impl not in WARD_IMPLS:
        raise ValueError(f"ward impl must be one of {WARD_IMPLS}, "
                         f"got {impl!r}")
    if impl == "auto":
        return "kernel" if _on_tpu() else "ref"
    return impl


@functools.partial(jax.jit, static_argnames=("factor", "block_b"))
def _ward_assign_kernel(x, mask, factor: int, block_b: int = 8):
    B = x.shape[0]
    # the reference's own initial distances, so both paths merge
    # identical values; padded docs are all-masked (all +inf)
    d2 = _pad_to(jax.vmap(ward_distances)(x, mask), 0, block_b,
                 value=jnp.inf)
    mp = _pad_to(mask, 0, block_b)
    n_valid = jnp.sum(mp.astype(jnp.int32), axis=-1)
    k = jnp.maximum(n_valid // factor + 1, 1)
    steps = jnp.maximum(n_valid - k, 0).reshape(-1, block_b).max(axis=1)
    out = ward_pool_pallas(d2, mp.astype(jnp.int32)[:, None, :],
                           k[:, None, None], steps, block_b=block_b,
                           interpret=not _on_tpu())
    return out[:B, 0]


def ward_assign(x, mask, factor: int, *, impl: str = "auto",
                block_b: int = 8):
    """Batched Ward cluster assignments, reference-bitwise.

    x [B, N, d], mask [B, N] -> assign [B, N] int32 where each valid
    token's id is its cluster's representative (lowest) token index —
    the exact contract of ``ward_cluster_batch``.
    """
    with jax.named_scope("ward"):       # op metadata only
        if resolve_impl(impl) == "ref":
            return ward_assign_ref(x, mask, factor)
        return _ward_assign_kernel(x, mask, int(factor), block_b)
