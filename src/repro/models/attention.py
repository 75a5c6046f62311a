"""The encoder's attention: multi-head self-attention over a padded
batch, global or banded, with learned or RoPE positions.

Two execution paths, picked per call:

1. ``masked`` — on the TPU, a padded input longer than
   ``attn_full_threshold`` runs the Pallas masked kernel
   (``kernels/flash_attention/masked.py``), which never forms an [S, S]
   score matrix and skips key blocks past each doc's last valid token.
2. ``full`` — everything else: materialized f32 scores, the band and the
   pad mask applied, softmax (fully padded query rows come out zero).

A local layer of an alternating model (ModernBERT) attends only keys with
|i - j| <= ``local_window`` and rotates at ``local_rope_theta``. Fewer
KV heads than query heads (``n_kv_heads``) are repeated up to the full
head count before either path.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.models.layers import dense, init_dense


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float):
    half = d_head // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, dh]; positions: [S] (broadcast over leading dims)."""
    dh = x.shape[-1]
    assert dh % 2 == 0, "RoPE requires even head dim"
    freqs = rope_freqs(dh, theta)                            # [dh/2]
    ang = positions.astype(jnp.float32)[..., None] * freqs   # [S, dh/2]
    cos = jnp.cos(ang)[..., None, :]                         # [S, 1, dh/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def init_attention(key, cfg, dtype=jnp.float32):
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, H * dh, dtype=dtype),
        "wk": init_dense(ks[1], d, KV * dh, dtype=dtype),
        "wv": init_dense(ks[2], d, KV * dh, dtype=dtype),
        "wo": init_dense(ks[3], H * dh, d, dtype=dtype),
    }


def _project_qkv(p, x, cfg, positions, theta=None):
    """Returns q [B,S,H,dh], k,v [B,S,KV,dh] with RoPE applied (at
    ``theta``, the config's ``rope_theta`` if None)."""
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = dense(p["wq"], x).reshape(B, S, H, dh)
    k = dense(p["wk"], x).reshape(B, S, KV, dh)
    v = dense(p["wv"], x).reshape(B, S, KV, dh)
    if cfg.pos_emb == "rope":
        theta = cfg.rope_theta if theta is None else theta
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def _repeat_kv(k, n_rep):
    """[B,S,KV,dh] -> [B,S,KV*n_rep,dh] (head-major repeat)."""
    if n_rep == 1:
        return k
    B, S, KV, dh = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, n_rep, dh))
    return k.reshape(B, S, KV * n_rep, dh)


# ---------------------------------------------------------------------------
# Full attention (materialized scores)
# ---------------------------------------------------------------------------
def _full_attn(q, k, v, *, pad_mask=None, window=0):
    """q,k,v: [B,S,H,dh]; pad_mask [B,S] True=valid key; ``window`` > 0
    keeps only keys with |i - j| <= window. -> [B,S,H,dh]"""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    Sq, Skv = q.shape[1], k.shape[1]
    if window:
        band = (jnp.abs(jnp.arange(Sq)[:, None] - jnp.arange(Skv)[None, :])
                <= window)
        s = jnp.where(band[None, None], s, -jnp.inf)
    if pad_mask is not None:
        s = jnp.where(pad_mask[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)   # fully-masked (padded) query rows
    return jnp.einsum("bhqs,bshd->bqhd", w.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Public forward
# ---------------------------------------------------------------------------
def attention_forward(p, x, cfg, *, positions, pad_mask=None, local=False):
    """x: [B, S, d_model] -> y [B, S, d_model].

    ``local`` runs a local layer of an alternating model (keys within
    ``cfg.local_window``, RoPE at ``cfg.local_rope_theta``)."""
    B, S, _ = x.shape
    window = cfg.local_window if local else 0
    theta = (cfg.local_rope_theta or cfg.rope_theta) if local else None
    q, k, v = _project_qkv(p, x, cfg, positions, theta)
    k = _repeat_kv(k, cfg.q_per_kv)
    v = _repeat_kv(v, cfg.q_per_kv)
    # alternating models: each layer's attention core under its kind's
    # scope (encoder/attention/global, encoder/attention/local)
    scope = (jax.named_scope("local" if local else "global")
             if cfg.local_window else contextlib.nullcontext())
    with scope:
        if (pad_mask is not None and S > cfg.attn_full_threshold
                and _on_tpu()):
            from repro.kernels.flash_attention.masked import masked_attention
            o = masked_attention(q, k, v, pad_mask, window=window)
        else:
            o = _full_attn(q, k, v, pad_mask=pad_mask, window=window)
    return dense(p["wo"], o.reshape(B, S, cfg.n_heads * cfg.d_head))
