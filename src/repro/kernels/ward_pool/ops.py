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

Which kernel, and how many docs a program holds, follow from the doc
width N and a VMEM budget (``ward_block_b``): the resident kernel keeps
``block_b`` whole [N, N] matrices with the merge step's [N, N]
temporaries (8 f32 copies a doc, input double-buffering included), up
to 8 docs; where not even one doc fits the budget (N > 724), the
long-doc kernel holds one doc's matrix in VMEM as column stripes of 128
lanes and keeps every row's minimum exact, so a merge touches rows i
and j, two stripes and the few rows it must read again, not the whole
matrix (``ward_pool_rows_pallas``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ward import ward_distances
from repro.kernels.maxsim.ops import _on_tpu, _pad_to
from repro.kernels.ward_pool.kernel import (ward_pool_pallas,
                                            ward_pool_rows_pallas)
from repro.kernels.ward_pool.ref import ward_assign_ref

WARD_IMPLS = ("auto", "kernel", "ref")
VMEM_BUDGET = 16 << 20      # bytes: v5e's default scoped VMEM limit
RESIDENT_COPIES = 8         # f32 [N, N] copies the resident kernel holds
MAX_BLOCK_B = 8
LANES = 128                 # column stripe width of the long-doc kernel


def resident(N: int) -> bool:
    """Whether one doc's [N, N] matrix and the merge step's temporaries
    fit the budget (the resident kernel), else the long-doc kernel."""
    return RESIDENT_COPIES * 4 * N * N <= VMEM_BUDGET


def ward_block_b(N: int) -> int:
    """Docs per program at doc width N (1 for the long-doc kernel)."""
    return max(1, min(MAX_BLOCK_B, VMEM_BUDGET // (RESIDENT_COPIES * 4
                                                   * N * N)))


def resolve_impl(impl: str) -> str:
    """'auto'|'kernel'|'ref' -> 'kernel'|'ref'."""
    if impl not in WARD_IMPLS:
        raise ValueError(f"ward impl must be one of {WARD_IMPLS}, "
                         f"got {impl!r}")
    if impl == "auto":
        return "kernel" if _on_tpu() else "ref"
    return impl


@functools.partial(jax.jit,
                   static_argnames=("factor", "block_b", "lanes"))
def _ward_assign_kernel(x, mask, factor: int, block_b: int = 8,
                        lanes: int = 0):
    """-> (assign [B, N], rescans [B]: the long-doc kernel's count of
    rows read again; None from the resident kernel)."""
    B, N = mask.shape
    # the reference's own initial distances, so both paths merge
    # identical values; padded docs and tokens are masked (all +inf)
    d2 = jax.vmap(ward_distances)(x, mask)
    if lanes:
        mult = lanes * 8 // math.gcd(lanes, 8)
        d2 = _pad_to(_pad_to(d2, 1, mult, value=jnp.inf), 2, mult,
                     value=jnp.inf)
        mp = _pad_to(mask, 1, mult)
    else:
        d2 = _pad_to(d2, 0, block_b, value=jnp.inf)
        mp = _pad_to(mask, 0, block_b)
    mp = mp.astype(jnp.int32)
    n_valid = jnp.sum(mp, axis=-1)
    k = jnp.maximum(n_valid // factor + 1, 1)
    steps = jnp.maximum(n_valid - k, 0)
    if lanes:
        out, rescans = ward_pool_rows_pallas(
            d2, mp.reshape(B, -1, lanes), k[:, None, None], steps,
            lanes=lanes, interpret=not _on_tpu())
        return out.reshape(B, -1)[:, :N], rescans[:, 0, 0]
    out = ward_pool_pallas(d2, mp[:, None, :], k[:, None, None],
                           steps.reshape(-1, block_b).max(axis=1),
                           block_b=block_b, interpret=not _on_tpu())
    return out[:B, 0, :N], None


def ward_assign(x, mask, factor: int, *, impl: str = "auto",
                block_b: Optional[int] = None, lanes: Optional[int] = None):
    """Batched Ward cluster assignments, reference-bitwise.

    x [B, N, d], mask [B, N] -> assign [B, N] int32 where each valid
    token's id is its cluster's representative (lowest) token index —
    the exact contract of ``ward_cluster_batch``. ``block_b`` (resident
    kernel) and ``lanes`` (long-doc kernel) default to what N gives
    (module doc); ``lanes`` chooses the long-doc kernel.
    """
    N = mask.shape[1]
    if lanes is None:
        lanes = 0 if resident(N) else LANES
    with jax.named_scope("ward"):       # op metadata only
        if resolve_impl(impl) == "ref":
            return ward_assign_ref(x, mask, factor)
        return _ward_assign_kernel(x, mask, int(factor),
                                   block_b or ward_block_b(N), lanes)[0]


def ward_rescans(x, mask, factor: int, *, lanes: int = LANES):
    """The long-doc kernel at any N: (assign [B, N], rescans [B] int32,
    each doc's rows read again to keep its row minima exact). For tests
    and the chip smoke; the build path drops the counts (reading them
    back would stall each batch on the host)."""
    with jax.named_scope("ward"):
        return _ward_assign_kernel(x, mask, int(factor), 1, lanes)
