"""Dispatcher for the fused centroid-interaction probe op.

``impl``:
  * ``"auto"``   — Pallas kernel on TPU, jnp reference elsewhere (the
                   serving default: interpret-mode Pallas on CPU is
                   correctness-only and would tank QPS).
  * ``"kernel"`` — force the Pallas kernel (interpret off-TPU; parity
                   tests and benches).
  * ``"ref"``    — force the jnp reference.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.maxsim.kernel import pad_slots
from repro.kernels.maxsim.ops import _on_tpu, _pad_axis_to, _q_mask_col
from repro.kernels.plaid_probe.kernel import plaid_probe_pallas
from repro.kernels.plaid_probe.ref import plaid_probe_ref

PROBE_IMPLS = ("auto", "kernel", "ref")


def plaid_probe_scores(q, q_mask, centroids, codes, code_mask, cand_mask,
                       *, t_cs: float, impl: str = "auto",
                       block_c: int = 8):
    """Approx (centroid-only, t_cs-pruned) MaxSim for gathered candidate
    code rows: q [Nq, Lq, dim]; codes/code_mask [Nq, C, L]; cand_mask
    [Nq, C] -> scores [Nq, C] f32 (-inf invalid)."""
    assert impl in PROBE_IMPLS, impl
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return plaid_probe_ref(q, q_mask, centroids, codes, code_mask,
                               cand_mask, t_cs=t_cs)
    C = codes.shape[1]
    n = pad_slots(C, block_c)

    def pad(x):
        return _pad_axis_to(x, 1, n).astype(jnp.int32)

    out = plaid_probe_pallas(
        jnp.asarray(q, jnp.float32), _q_mask_col(q_mask),
        jnp.asarray(centroids, jnp.float32).T, pad(codes),
        pad(code_mask), t_cs=float(t_cs), block_c=block_c,
        interpret=not _on_tpu())
    return jnp.where(cand_mask, out[:, 0, :C], -jnp.inf)
