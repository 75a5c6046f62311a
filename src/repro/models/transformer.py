"""The encoder trunk: a bidirectional pre-norm transformer, scan-over-layers.

Layers are stored *stacked* (leading layer axis, ``dense_layers``) and
applied with ``jax.lax.scan``, so the compiled HLO holds one layer body
whatever the depth. Positions are learned (``pos_embed``, BERT) or RoPE
(ModernBERT). An alternating model (``local_window`` > 0) mixes global
layers with banded local ones (``_alternating_stack``).

``forward`` maps token ids and a key-padding mask to hidden states; the
ColBERT head (``models/colbert.py``) projects them to unit vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.attention import attention_forward, init_attention
from repro.models.layers import dt, embed, init_embed, init_norm, norm
from repro.models.mlp import init_mlp, mlp


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
# Keys are split four ways (two unused): every weight keeps the draw that
# seeded indexes and pinned encoder outputs were made with.
def _init_layer(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "attn_norm": init_norm(cfg.norm, cfg.d_model, dtype, cfg.norm_bias),
        "attn": init_attention(ks[0], cfg, dtype),
        "mlp_norm": init_norm(cfg.norm, cfg.d_model, dtype, cfg.norm_bias),
        "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp,
                        dtype=dtype),
    }


def init_transformer(key, cfg):
    dtype = dt(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    params = {"embed": init_embed(ks[0], cfg.vocab_size, cfg.d_model,
                                  dtype=dtype)}
    if cfg.embed_norm:
        params["embed_norm"] = init_norm(cfg.norm, cfg.d_model, dtype,
                                         cfg.norm_bias)
    if cfg.pos_emb == "learned":
        params["pos_embed"] = init_embed(
            jax.random.fold_in(ks[0], 7), cfg.max_seq_len, cfg.d_model,
            dtype=dtype)
    lk = jax.random.split(ks[1], cfg.n_layers)
    params["dense_layers"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype))(lk)
    params["final_norm"] = init_norm(cfg.norm, cfg.d_model, dtype,
                                     cfg.norm_bias)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _block(x, lp, cfg, *, positions, pad_mask, local=False, attn_norm=True):
    # named scopes only label the ops' metadata (device trace)
    with jax.named_scope("attention"):
        h = norm(cfg.norm, lp["attn_norm"], x, cfg.norm_eps) if attn_norm \
            else x
        h = attention_forward(lp["attn"], h, cfg, positions=positions,
                              pad_mask=pad_mask, local=local)
        x = x + h
    with jax.named_scope("mlp"):
        h = norm(cfg.norm, lp["mlp_norm"], x, cfg.norm_eps)
        h = mlp(lp["mlp"], h, cfg.act, cfg.gated_mlp)
    return x + h


def _scan_stack(x, stack, cfg, *, positions, pad_mask):
    """Every layer global: one scan over the stacked layers."""
    block = functools.partial(_block, cfg=cfg, positions=positions,
                              pad_mask=pad_mask)
    if cfg.remat:
        block = jax.checkpoint(block)
    x, _ = jax.lax.scan(lambda x, lp: (block(x, lp), None), x, stack)
    return x


def _alternating_stack(x, stack, cfg, *, positions, pad_mask):
    """The stack of an alternating model (ModernBERT): layer i is
    global when ``i % global_every == 0``. Layer 0 (global, without its
    pre-attention norm when ``first_attn_norm`` is off) runs alone, then
    one scan over the periods of ``global_every - 1`` local layers and a
    global one, then any layers left over."""
    P, n = cfg.global_every, cfg.n_layers

    def block(x, lp, i):
        fn = functools.partial(_block, cfg=cfg, positions=positions,
                               pad_mask=pad_mask,
                               local=not cfg.is_global(i),
                               attn_norm=i > 0 or cfg.first_attn_norm)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        return fn(x, lp)

    def layer(i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            stack)

    def period(x, p):
        for r in range(P):      # layer 1 + p * P + r
            x = block(x, layer(1 + p * P + r), 1 + r)
        return x, None

    x = block(x, layer(0), 0)
    m = (n - 1) // P
    x, _ = jax.lax.scan(period, x, jnp.arange(m))
    for i in range(1 + m * P, n):
        x = block(x, layer(i), i)
    return x


# ---------------------------------------------------------------------------
# Forward (hidden states)
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg, *, pad_mask=None):
    """tokens [B, S] int32, pad_mask [B, S] (True = a real token, None =
    all) -> hidden states [B, S, d_model]."""
    cdt = dt(cfg.dtype)
    x = embed(params["embed"], tokens, dtype=cdt)
    positions = jnp.arange(tokens.shape[1])
    if cfg.pos_emb == "learned":
        x = x + embed(params["pos_embed"], positions, dtype=cdt)
    if cfg.embed_norm:
        x = norm(cfg.norm, params["embed_norm"], x, cfg.norm_eps)
    stack = _alternating_stack if cfg.local_window else _scan_stack
    x = stack(x, params["dense_layers"], cfg, positions=positions,
              pad_mask=pad_mask)
    return norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
