"""jit'd public wrappers for the MaxSim kernels: pad the doc axis to the
kernels' slot count (padded docs are fully masked), lay the masks out as
the kernels expect, dispatch (interpret=True off-TPU), unpad."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.maxsim.kernel import (maxsim_pallas, maxsim_rerank_pallas,
                                        pad_slots)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, axis, mult, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_axis_to(x, axis, n, value=0):
    """Pad ``axis`` of x up to length n."""
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, widths, constant_values=value)


def _q_mask_col(q_mask):
    """[Nq, Lq] bool -> the kernels' [Nq, Lq, 1] int32 column layout."""
    return jnp.asarray(q_mask).astype(jnp.int32)[:, :, None]


@functools.partial(jax.jit, static_argnames=("block_d",))
def maxsim(q, q_mask, d, d_mask, *, block_d: int = 8):
    """Late-interaction scores [Nq, Nd] via the Pallas kernel."""
    Nd = d.shape[0]
    n = pad_slots(Nd, block_d)
    d = _pad_axis_to(d, 0, n)
    d_mask = _pad_axis_to(d_mask, 0, n).astype(jnp.int32)
    out = maxsim_pallas(q, _q_mask_col(q_mask), d, d_mask, block_d=block_d,
                        interpret=not _on_tpu())
    return out[:, 0, :Nd]


@functools.partial(jax.jit, static_argnames=("block_s",))
def maxsim_rerank(q, q_mask, d, d_mask, *, block_s: int = 8):
    """Per-query candidate scores [Nq, S]: d is a per-query gather
    [Nq, S, Ld, dim] and query i only scores slab d[i]."""
    S = d.shape[1]
    n = pad_slots(S, block_s)
    d = _pad_axis_to(d, 1, n)
    d_mask = _pad_axis_to(d_mask, 1, n).astype(jnp.int32)
    out = maxsim_rerank_pallas(q, _q_mask_col(q_mask), d, d_mask,
                               block_s=block_s, interpret=not _on_tpu())
    return out[:, 0, :S]
