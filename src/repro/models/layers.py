"""Minimal functional layer substrate (no flax): param trees + apply fns.

Parameters are nested dicts of jnp arrays. Every layer exposes
``init_<layer>(key, ...) -> params`` and a pure ``<layer>(params, x, ...)``.
Compute dtype is controlled by the caller (params stay in param_dtype,
activations are cast on entry).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def dt(name: str):
    return DTYPES[name]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def trunc_normal(key, shape, std=0.02, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------
def init_dense(key, d_in, d_out, bias=False, std=None, dtype=jnp.float32):
    kw, kb = jax.random.split(key)
    std = std if std is not None else 1.0 / np.sqrt(d_in)
    p = {"w": (jax.random.normal(kw, (d_in, d_out), jnp.float32) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, dtype=None):
    dtype = dtype if dtype is not None else x.dtype
    w = p["w"].astype(dtype)
    y = x.astype(dtype) @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_rmsnorm(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d, dtype=jnp.float32, bias=True):
    p = {"scale": jnp.ones((d,), dtype)}
    if bias:
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def layernorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:             # a bias-free LayerNorm holds no bias
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def init_norm(kind, d, dtype=jnp.float32, bias=True):
    if kind == "rmsnorm":
        return init_rmsnorm(d, dtype)
    return init_layernorm(d, dtype, bias)


def norm(kind, p, x, eps=1e-6):
    return rmsnorm(p, x, eps) if kind == "rmsnorm" else layernorm(p, x, eps)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
def init_embed(key, vocab, d, std=0.02, dtype=jnp.float32):
    return {"table": trunc_normal(key, (vocab, d), std, dtype)}


def embed(p, ids, dtype=None):
    t = p["table"]
    if dtype is not None:
        t = t.astype(dtype)
    return jnp.take(t, ids, axis=0)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,                # tanh approximation
    "gelu_erf": lambda x: jax.nn.gelu(x, approximate=False),
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
}


def act_fn(name):
    return ACTS[name]


# ---------------------------------------------------------------------------
# Tree utilities
# ---------------------------------------------------------------------------
def tree_paths(tree):
    """Yield ('a/b/c', leaf) pairs for a nested dict/list pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                parts.append(str(p.key))
            elif isinstance(p, jax.tree_util.SequenceKey):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        out.append(("/".join(parts), leaf))
    return out
