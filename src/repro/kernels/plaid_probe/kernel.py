"""PLAID centroid-interaction Pallas TPU kernel (stage 3), doc-major.

With ``t_cs >= 0`` every pruned centroid score is >= 0 (masked query
tokens score 0), so a doc's centroid-only score depends only on the SET
of centroids its tokens use (PLAID's "bag of centroid ids",
arXiv:2205.09707 §4):

    score(q, d) = sum_t max_k csp[q, t, k] * member[k, d]

An absent centroid contributes 0, which never beats a real score, and
``x * 1.0`` / ``x * 0.0`` are exact, so the per-token max equals the
max over the doc's own tokens bit for bit. The query tokens are summed
in ``fold_sum``'s order, the order the host path's stage 3
(``core.plaid._approx_scores_batch``) sums in too, so the scores equal
the host path's exactly (``core.plaid.device_probe_plan`` engages the
device path only where this holds).

Every doc of the index is scored from the 0/1 ``[K, n_docs]``
membership table (``DeviceInvertedLists.doc_member``) on the VPU, a
multiply and a max per element: no MXU pass, no gathered code rows.
Grid: one program per tile of ``block_d`` docs; the pruned scores of the
whole batch stay resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.plaid_probe.ref import fold_sum


def _plaid_probe_bag_kernel(csp_ref, mem_ref, o_ref):
    """Every query x one tile of docs: csp [Nq, Lq, K] pruned centroid
    scores (>= 0), member [K, Bd] 0/1 -> o [Nq, Bd]."""
    nq, lq, n_cent = csp_ref.shape
    bd = mem_ref.shape[1]

    def one_query(q, carry):
        cs = csp_ref[q]                                     # [Lq, K]
        acc = jnp.zeros((lq, bd), jnp.float32)
        for k in range(n_cent):
            acc = jnp.maximum(acc, cs[:, k:k + 1] * mem_ref[k:k + 1, :])
        o_ref[pl.ds(q, 1), :] = fold_sum(acc, 0)
        return carry

    jax.lax.fori_loop(0, nq, one_query, 0)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def plaid_probe_bag_pallas(csp, doc_member, *, block_d: int = 1024,
                           interpret: bool = False):
    """csp [Nq, Lq, K] f32 t_cs-pruned centroid scores (>= 0, masked
    tokens 0); doc_member [K, n_docs] f32 0/1 -> bag scores [Nq, n_docs]
    f32. One program per tile of ``block_d`` docs (a multiple of 128);
    the ragged last tile is left to Pallas's boundary handling, since
    doc columns are independent."""
    Nq, Lq, K = csp.shape
    n_docs = doc_member.shape[1]
    bd = block_d if n_docs > block_d else n_docs
    return pl.pallas_call(
        _plaid_probe_bag_kernel,
        grid=(pl.cdiv(n_docs, bd),),
        in_specs=[
            pl.BlockSpec((Nq, Lq, K), lambda j: (0, 0, 0)),
            pl.BlockSpec((K, bd), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((Nq, bd), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((Nq, n_docs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="plaid_probe_bag_pallas",
    )(csp, doc_member)
