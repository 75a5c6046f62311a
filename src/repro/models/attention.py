"""Attention: GQA + RoPE + qk-norm + QKV-bias, three execution paths.

1. ``full``      — materialized scores, for short sequences / smoke tests.
2. ``chunked``   — online-softmax over KV chunks (FlashAttention recurrence in
                   pure jnp); causal variant unrolls over query chunks so each
                   query chunk only visits KV chunks at-or-below the diagonal
                   (exact FLOPs — no above-diagonal chunk pair is computed).
3. ``decode``    — one new token vs a KV cache; exact two-pass softmax that the
                   SPMD partitioner turns into flash-decoding style partial
                   max/sum all-reduces when the cache is sequence-sharded.

TP layout: for train/prefill, KV heads are repeated up to the full head count
so the head axis (H) shards cleanly over the TP mesh axis even when
KV < tp (kimi kv=8, tp=16). GQA still pays off — smaller wk/wv
projections — and the repeat is a free broadcast on TPU. Decode keeps the
grouped [KV, G] layout (repeating a 500k-token cache 8x would be absurd);
there the cache *sequence* axis is the sharded one.

For head counts that do not divide tp (qwen2.5-14b H=40), the sharding rules
switch to sequence parallelism ("qseq" -> model) and heads stay unsharded —
see ``sharding.api.lm_rules``.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.models.layers import dense, init_dense, init_rmsnorm, rmsnorm
from repro.sharding.api import constrain


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
    p = {
        "wq": init_dense(ks[0], d, H * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_dense(ks[1], d, KV * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_dense(ks[2], d, KV * dh, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_dense(ks[3], H * dh, d, bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(dh, dtype)
        p["k_norm"] = init_rmsnorm(dh, dtype)
    return p


def _project_qkv(p, x, cfg, positions, theta=None):
    """Returns q [B,S,H,dh], k,v [B,S,KV,dh] with RoPE/qk-norm applied
    (RoPE at ``theta``, the config's ``rope_theta`` if None)."""
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = dense(p["wq"], x).reshape(B, S, H, dh)
    k = dense(p["wk"], x).reshape(B, S, KV, dh)
    v = dense(p["wv"], x).reshape(B, S, KV, dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_emb == "rope":
        theta = cfg.rope_theta if theta is None else theta
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def _repeat_kv(k, n_rep):
    """[B,S,KV,dh] -> [B,S,KV*n_rep,dh] (head-major repeat, matches grouped)."""
    if n_rep == 1:
        return k
    B, S, KV, dh = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, n_rep, dh))
    return k.reshape(B, S, KV * n_rep, dh)


# ---------------------------------------------------------------------------
# Full attention (short sequences / masked encoder) — MHA layout
# ---------------------------------------------------------------------------
def _full_attn(q, k, v, *, causal, pad_mask=None, q_offset=0, window=0):
    """q,k,v: [B,S,H,dh]; pad_mask [B,Skv] True=valid; ``window`` > 0
    keeps only keys with |i - j| <= window. -> [B,Sq,H,dh]"""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    Sq, Skv = q.shape[1], k.shape[1]
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Skv)
        cm = qpos[:, None] >= kpos[None, :]
        s = jnp.where(cm[None, None], s, -jnp.inf)
    if window:
        qpos = jnp.arange(Sq) + q_offset
        band = jnp.abs(qpos[:, None] - jnp.arange(Skv)[None, :]) <= window
        s = jnp.where(band[None, None], s, -jnp.inf)
    if pad_mask is not None:
        s = jnp.where(pad_mask[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)   # fully-masked (padded) query rows
    o = jnp.einsum("bhqs,bshd->bqhd", w.astype(v.dtype), v)
    return o


# ---------------------------------------------------------------------------
# Chunked online-softmax attention — MHA layout
# ---------------------------------------------------------------------------
def _attn_over_kv_chunks(qc, k, v, *, n_chunks, chunk, causal, q_start,
                         unroll=False):
    """Online softmax over KV chunks for one query chunk.

    qc: [B,Cq,H,dh]; k,v: [B, n_chunks*chunk, H, dh]. -> [B,Cq,H,dh]
    """
    B, Cq, H, dh = qc.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    kc = k.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)

    def step(carry, inp):
        m, l, acc = carry
        ci, kci, vci = inp
        s = jnp.einsum("bqhd,bshd->bhqs", qc, kci,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jnp.arange(Cq)
            kpos = ci * chunk + jnp.arange(chunk)
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None],
                          s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        pmat = jnp.exp(s - m_safe[..., None])
        pmat = jnp.where(jnp.isneginf(s), 0.0, pmat)
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(jnp.isneginf(m), 0.0, alpha)
        l_new = l * alpha + jnp.sum(pmat, axis=-1)
        pv = jnp.einsum("bhqs,bshd->bhqd", pmat.astype(vci.dtype), vci)
        acc_new = acc * alpha[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Cq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Cq), jnp.float32)
    a0 = jnp.zeros((B, H, Cq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc),
        unroll=n_chunks if unroll else 1)
    l = jnp.where(l == 0.0, 1.0, l)
    o = acc / l[..., None]
    return o.transpose(0, 2, 1, 3).astype(qc.dtype)       # [B,Cq,H,dh]


def _chunked_attn(q, k, v, *, causal, chunk, unroll=False):
    """Exact-FLOPs chunked attention (see module docstring)."""
    B, S = q.shape[0], q.shape[1]
    assert S % chunk == 0, (S, chunk)
    nq = S // chunk
    if not causal:
        return _attn_over_kv_chunks(q, k, v, n_chunks=nq, chunk=chunk,
                                    causal=False, q_start=0, unroll=unroll)
    outs = []
    for i in range(nq):
        qc = jax.lax.slice_in_dim(q, i * chunk, (i + 1) * chunk, axis=1)
        kv_end = (i + 1) * chunk
        ki = jax.lax.slice_in_dim(k, 0, kv_end, axis=1)
        vi = jax.lax.slice_in_dim(v, 0, kv_end, axis=1)
        outs.append(_attn_over_kv_chunks(
            qc, ki, vi, n_chunks=i + 1, chunk=chunk, causal=True,
            q_start=i * chunk, unroll=unroll))
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Public forward (train / prefill)
# ---------------------------------------------------------------------------
def attention_forward(p, x, cfg, *, positions=None, pad_mask=None,
                      return_kv=False, local=False):
    """x: [B, S, d_model]. Returns y [B, S, d_model] (and (k, v) if asked).

    ``local`` runs a local layer of an alternating model (keys within
    ``cfg.local_window``, RoPE at ``cfg.local_rope_theta``). A padded
    bidirectional input longer than ``attn_full_threshold`` takes the
    masked kernel (``kernels/flash_attention/masked.py``) on the TPU and
    never forms an [S, S] score matrix there."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    window = cfg.local_window if local else 0
    theta = (cfg.local_rope_theta or cfg.rope_theta) if local else None
    q, k, v = _project_qkv(p, x, cfg, positions, theta)
    kv_out = (k, v)
    kf = _repeat_kv(k, cfg.q_per_kv)
    vf = _repeat_kv(v, cfg.q_per_kv)
    q = constrain(q, "batch", "qseq", "heads", None)
    kf = constrain(kf, "batch", "kvseq", "heads", None)
    vf = constrain(vf, "batch", "kvseq", "heads", None)

    use_full = (S <= cfg.attn_full_threshold or S % cfg.attn_chunk != 0
                or pad_mask is not None or window > 0)
    masked_long = (pad_mask is not None and not cfg.causal
                   and S > cfg.attn_full_threshold)
    # alternating models: each layer's attention core under its kind's
    # scope (encoder/attention/global, encoder/attention/local)
    scope = (jax.named_scope("local" if local else "global")
             if cfg.local_window else contextlib.nullcontext())
    with scope:
        if masked_long and _on_tpu():
            from repro.kernels.flash_attention.masked import masked_attention
            o = masked_attention(q, kf, vf, pad_mask, window=window)
        elif cfg.use_flash_kernel and pad_mask is None and cfg.causal:
            from repro.kernels.flash_attention.ops import flash_attention
            o = flash_attention(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), causal=True)
            o = o.transpose(0, 2, 1, 3)
        elif use_full:
            o = _full_attn(q, kf, vf, causal=cfg.causal, pad_mask=pad_mask,
                           window=window)
        else:
            o = _chunked_attn(q, kf, vf, causal=cfg.causal,
                              chunk=cfg.attn_chunk, unroll=cfg.unroll_scans)
    o = o.reshape(B, S, cfg.n_heads * cfg.d_head)
    o = constrain(o, "batch", "qseq", "heads")
    y = dense(p["wo"], o)
    y = constrain(y, "batch", "seq", "dmodel")
    if return_kv:
        return y, kv_out
    return y


# ---------------------------------------------------------------------------
# Decode (one token vs KV cache) — grouped GQA layout, cache seq-sharded
# ---------------------------------------------------------------------------
def attention_decode(p, x, cfg, cache_k, cache_v, pos):
    """x: [B, 1, d]; cache_[kv]: [B, S_max, KV, dh]; pos: scalar int32 —
    number of valid cache entries (the new token is written at ``pos``).

    Returns (y [B,1,d], new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    KV, G, dh = cfg.n_kv_heads, cfg.q_per_kv, cfg.d_head
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    q = q.reshape(B, 1, KV, G, dh)

    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, pos, 0, 0))
    cache_k = constrain(cache_k, "batch", "kvseq", "kv", None)
    cache_v = constrain(cache_v, "batch", "kvseq", "kv", None)

    S = cache_k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, cache_k,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S) <= pos
    s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
    # Exact two-pass softmax: reductions over the (possibly sequence-sharded)
    # cache axis become two small all-reduces under SPMD (flash-decoding).
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    e = jnp.where(jnp.isneginf(s), 0.0, e)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    w = e / denom
    o = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(cache_v.dtype), cache_v)
    o = o.reshape(B, 1, cfg.n_heads * dh)
    y = dense(p["wo"], o)
    y = constrain(y, "batch", "seq", "dmodel")
    return y, cache_k, cache_v
