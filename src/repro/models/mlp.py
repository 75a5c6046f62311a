"""Dense FFN: SwiGLU/GeGLU-style gated or plain 2-layer MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, dense, init_dense


def init_mlp(key, d_model, d_ff, gated=True, bias=False, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "w1": init_dense(ks[0], d_model, d_ff, bias=bias, dtype=dtype),
        "w2": init_dense(ks[1], d_ff, d_model, bias=bias, dtype=dtype),
    }
    if gated:
        p["w3"] = init_dense(ks[2], d_model, d_ff, bias=bias, dtype=dtype)
    return p


def mlp(p, x, act="silu", gated=True):
    h = act_fn(act)(dense(p["w1"], x))
    if gated:
        h = h * dense(p["w3"], x)
    return dense(p["w2"], h)
