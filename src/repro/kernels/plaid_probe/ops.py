"""Dispatcher for the doc-major centroid-interaction probe op.

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

from repro.kernels.maxsim.ops import _on_tpu
from repro.kernels.plaid_probe.kernel import plaid_probe_bag_pallas
from repro.kernels.plaid_probe.ref import plaid_probe_bag_ref

PROBE_IMPLS = ("auto", "kernel", "ref")


def plaid_probe_bag_scores(csp, doc_member, *, impl: str = "auto"):
    """Bag (doc-major) centroid-only scores of every doc: csp [Nq, Lq, K]
    t_cs-pruned centroid scores, all >= 0; doc_member [K, n_docs] 0/1
    -> [Nq, n_docs] f32."""
    assert impl in PROBE_IMPLS, impl
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return plaid_probe_bag_ref(csp, doc_member)
    return plaid_probe_bag_pallas(
        jnp.asarray(csp, jnp.float32), jnp.asarray(doc_member, jnp.float32),
        interpret=not _on_tpu())
