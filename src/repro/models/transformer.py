"""TransformerLM trunk: causal LM and bidirectional encoder, scan-over-layers.

Layers are stored *stacked* (leading layer axis) and applied with
``jax.lax.scan`` so the compiled HLO contains one layer body regardless of
depth — essential to keep 61-layer / 1T-param dry-run compiles tractable.
MoE models with ``first_dense_layers > 0`` hold two stacks (dense prefix +
MoE suffix), each scanned.

Step functions:
  * ``forward``      — hidden states (encoder use / ColBERT trunk)
  * ``lm_loss``      — causal LM loss with seq-chunked vocab projection
  * ``prefill``      — forward + populated KV cache
  * ``decode_step``  — one token against the cache (serve_step)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.attention import (attention_decode, attention_forward,
                                    init_attention)
from repro.models.layers import (dense, dt, embed, init_dense, init_embed,
                                 init_norm, norm)
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe_apply
from repro.sharding.api import constrain


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg, is_moe, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "attn_norm": init_norm(cfg.norm, cfg.d_model, dtype, cfg.norm_bias),
        "attn": init_attention(ks[0], cfg, dtype),
        "mlp_norm": init_norm(cfg.norm, cfg.d_model, dtype, cfg.norm_bias),
    }
    if is_moe:
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp,
                            dtype=dtype)
    return p


def init_transformer(key, cfg):
    dtype = dt(cfg.param_dtype)
    n_moe = max(cfg.n_layers - cfg.first_dense_layers, 0) if cfg.moe else 0
    n_dense = cfg.n_layers - n_moe
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
    if n_dense > 0:
        lk = jax.random.split(ks[1], n_dense)
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, False, dtype))(lk)
    if n_moe > 0:
        lk = jax.random.split(ks[2], n_moe)
        params["moe_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, True, dtype))(lk)
    params["final_norm"] = init_norm(cfg.norm, cfg.d_model, dtype,
                                     cfg.norm_bias)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(
            ks[3], cfg.d_model, cfg.vocab_size, dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _block(x, lp, cfg, *, is_moe, moe_impl, positions, pad_mask,
           local=False, attn_norm=True):
    # named scopes only label the ops' metadata (device trace)
    with jax.named_scope("attention"):
        h = norm(cfg.norm, lp["attn_norm"], x, cfg.norm_eps) if attn_norm \
            else x
        h = attention_forward(lp["attn"], h, cfg, positions=positions,
                              pad_mask=pad_mask, local=local)
        x = x + h
    with jax.named_scope("mlp"):
        h = norm(cfg.norm, lp["mlp_norm"], x, cfg.norm_eps)
        if is_moe:
            h, aux = moe_apply(lp["moe"], h, cfg, impl=moe_impl)
        else:
            h = mlp(lp["mlp"], h, cfg.act, cfg.gated_mlp)
            aux = jnp.zeros((), jnp.float32)
    # layer-boundary resharding point: under sequence parallelism
    # ("seq" -> model) the residual stream lives seq-sharded between
    # layers and XLA all-gathers/reduce-scatters around attn+mlp.
    x = constrain(x + h, "batch", "seq", "dmodel")
    return x, aux


def _scan_stack(x, stack, cfg, *, is_moe, moe_impl, positions, pad_mask):
    block = functools.partial(_block, cfg=cfg, is_moe=is_moe,
                              moe_impl=moe_impl, positions=positions,
                              pad_mask=pad_mask)
    if cfg.remat:
        block = jax.checkpoint(block)

    def body(carry, lp):
        x, aux = carry
        x, a = block(x, lp)
        return (x, aux + a), None

    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack,
                               unroll=n if cfg.unroll_scans else 1)
    return x, aux


def _alternating_stack(x, stack, cfg, *, positions, pad_mask):
    """The dense stack of an alternating model (ModernBERT): layer i is
    global when ``i % global_every == 0``. Layer 0 (global, without its
    pre-attention norm when ``first_attn_norm`` is off) runs alone, then
    one scan over the periods of ``global_every - 1`` local layers and a
    global one, then any layers left over."""
    P, n = cfg.global_every, cfg.n_layers

    def block(x, lp, i):
        fn = functools.partial(_block, cfg=cfg, is_moe=False, moe_impl=None,
                               positions=positions, pad_mask=pad_mask,
                               local=not cfg.is_global(i),
                               attn_norm=i > 0 or cfg.first_attn_norm)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        return fn(x, lp)[0]

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
    x, _ = jax.lax.scan(period, x, jnp.arange(m),
                        unroll=m if cfg.unroll_scans else 1)
    for i in range(1 + m * P, n):
        x = block(x, layer(i), i)
    return x


# ---------------------------------------------------------------------------
# Forward (hidden states)
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg, *, pad_mask=None, positions=None,
            moe_impl="capacity"):
    """tokens: [B, S] int32 -> hidden [B, S, d_model], aux_loss scalar."""
    cdt = dt(cfg.dtype)
    x = embed(params["embed"], tokens, dtype=cdt)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    if cfg.pos_emb == "learned":
        x = x + embed(params["pos_embed"], positions, dtype=cdt)
    if cfg.embed_norm:
        x = norm(cfg.norm, params["embed_norm"], x, cfg.norm_eps)
    x = constrain(x, "batch", "seq", "dmodel")
    aux = jnp.zeros((), jnp.float32)
    if cfg.local_window:
        x = _alternating_stack(x, params["dense_layers"], cfg,
                               positions=positions, pad_mask=pad_mask)
    elif "dense_layers" in params:
        x, a = _scan_stack(x, params["dense_layers"], cfg, is_moe=False,
                           moe_impl=moe_impl, positions=positions,
                           pad_mask=pad_mask)
        aux += a
    if "moe_layers" in params:
        x, a = _scan_stack(x, params["moe_layers"], cfg, is_moe=True,
                           moe_impl=moe_impl, positions=positions,
                           pad_mask=pad_mask)
        aux += a
    x = norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    return x, aux


def logits_head(params, hidden, cfg):
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(hidden.dtype)
        lg = hidden @ w.T
    else:
        lg = dense(params["lm_head"], hidden)
    return constrain(lg, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Loss (seq-chunked vocab projection)
# ---------------------------------------------------------------------------
def lm_loss(params, tokens, labels, cfg, *, loss_mask=None,
            moe_impl="capacity"):
    """Causal-LM cross entropy. tokens/labels: [B, S] (labels pre-shifted).

    The [B, S, V] logits tensor is never fully materialized: the head
    projection + xent run over sequence chunks inside a scan.
    """
    hidden, aux = forward(params, tokens, cfg, moe_impl=moe_impl)
    B, S, d = hidden.shape
    chunk = min(cfg.logits_chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    hc = hidden.reshape(B, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    if loss_mask is None:
        loss_mask = jnp.ones_like(labels, jnp.float32)
    mc = loss_mask.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        tot, cnt = carry
        h, lab, msk = inp
        lg = logits_head(params, h, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * msk
        return (tot + nll.sum(), cnt + msk.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, mc), unroll=n_chunks if cfg.unroll_scans else 1)
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss + aux, {"xent": loss, "aux": aux, "tokens": cnt}


# ---------------------------------------------------------------------------
# KV cache + prefill + decode
# ---------------------------------------------------------------------------
def init_cache(cfg, batch, max_len, dtype=None):
    dtype = dtype or dt(cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _stacked_layers(params, cfg):
    """Concatenate dense+moe stacks into one per-layer iterable view.

    Returns list of (stack_params, is_moe, n_layers) segments in order.
    """
    segs = []
    if "dense_layers" in params:
        n = jax.tree_util.tree_leaves(params["dense_layers"])[0].shape[0]
        segs.append((params["dense_layers"], False, n))
    if "moe_layers" in params:
        n = jax.tree_util.tree_leaves(params["moe_layers"])[0].shape[0]
        segs.append((params["moe_layers"], True, n))
    return segs


def prefill(params, tokens, cfg, *, max_len=None, moe_impl="capacity"):
    """Encode a prompt, returning (hidden, cache filled up to S).

    Cache is produced by re-running the per-layer kv projections inside the
    scan, emitted as stacked ys.
    """
    cdt = dt(cfg.dtype)
    B, S = tokens.shape
    max_len = max_len or S
    x = embed(params["embed"], tokens, dtype=cdt)
    positions = jnp.arange(S)
    if cfg.pos_emb == "learned":
        x = x + embed(params["pos_embed"], positions, dtype=cdt)
    x = constrain(x, "batch", "seq", "dmodel")

    def seg_body(x, lp, is_moe):
        h = norm(cfg.norm, lp["attn_norm"], x, cfg.norm_eps)
        h, (k, v) = attention_forward(lp["attn"], h, cfg, positions=positions,
                                      return_kv=True)
        x = x + h
        h2 = norm(cfg.norm, lp["mlp_norm"], x, cfg.norm_eps)
        if is_moe:
            h2, _ = moe_apply(lp["moe"], h2, cfg, impl=moe_impl)
        else:
            h2 = mlp(lp["mlp"], h2, cfg.act, cfg.gated_mlp)
        x = x + h2
        if max_len > S:
            pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        k = constrain(k, "batch", "cacheseq", "kv", None)
        v = constrain(v, "batch", "cacheseq", "kv", None)
        return x, (k.astype(cdt), v.astype(cdt))

    ks, vs = [], []
    for stack, is_moe, _n in _stacked_layers(params, cfg):
        body = functools.partial(seg_body, is_moe=is_moe)
        if cfg.remat:
            body = jax.checkpoint(body)

        def scan_fn(x, lp):
            x, kv = body(x, lp)
            return x, kv

        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        x, (k_seg, v_seg) = jax.lax.scan(
            scan_fn, x, stack, unroll=n if cfg.unroll_scans else 1)
        ks.append(k_seg)
        vs.append(v_seg)
    cache = {"k": jnp.concatenate(ks, axis=0), "v": jnp.concatenate(vs, axis=0)}
    x = norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    return x, cache


def decode_step(params, token, cache, pos, cfg, *, moe_impl="capacity"):
    """token: [B, 1] int32; cache: stacked {k,v} [L,B,Smax,KV,dh]; pos scalar.

    Returns (logits [B, 1, V], new cache).
    """
    cdt = dt(cfg.dtype)
    x = embed(params["embed"], token, dtype=cdt)
    if cfg.pos_emb == "learned":
        x = x + embed(params["pos_embed"], jnp.full((1,), pos), dtype=cdt)
    x = constrain(x, "batch", "seq", "dmodel")

    layer_off = 0
    new_k, new_v = [], []
    for stack, is_moe, n in _stacked_layers(params, cfg):
        ck = jax.lax.dynamic_slice_in_dim(cache["k"], layer_off, n, axis=0)
        cv = jax.lax.dynamic_slice_in_dim(cache["v"], layer_off, n, axis=0)

        def body(x, inp, is_moe=is_moe):
            lp, k_l, v_l = inp
            h = norm(cfg.norm, lp["attn_norm"], x, cfg.norm_eps)
            h, k_l, v_l = attention_decode(lp["attn"], h, cfg, k_l, v_l, pos)
            x = x + h
            h2 = norm(cfg.norm, lp["mlp_norm"], x, cfg.norm_eps)
            if is_moe:
                h2, _ = moe_apply(lp["moe"], h2, cfg, impl=moe_impl)
            else:
                h2 = mlp(lp["mlp"], h2, cfg.act, cfg.gated_mlp)
            return x + h2, (k_l, v_l)

        x, (k_seg, v_seg) = jax.lax.scan(
            body, x, (stack, ck, cv), unroll=n if cfg.unroll_scans else 1)
        new_k.append(k_seg)
        new_v.append(v_seg)
        layer_off += n
    cache = {"k": jnp.concatenate(new_k, axis=0),
             "v": jnp.concatenate(new_v, axis=0)}
    x = norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = logits_head(params, x, cfg)
    return logits, cache
