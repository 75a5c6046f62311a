"""ColBERT encoder (Khattab & Zaharia, 2020): late-interaction over BERT.

Puts the ColBERT head on the encoder trunk (``models/transformer.py``:
BERT-style learned positions for ColBERTv2, RoPE and alternating
global/local layers for ModernBERT):

  * ``[Q]``/``[D]`` marker token inserted after [CLS] (query vs document).
  * Queries are *expanded*: padded to ``query_maxlen`` with [MASK] tokens
    that DO attend and DO emit vectors (ColBERT's query augmentation).
  * Linear projection d_model -> proj_dim (128), L2-normalized.
  * Document punctuation tokens are masked out of the stored vector set.

Training: in-batch-negative contrastive loss over MaxSim scores — the
standard ColBERTv2-style objective (without distillation, which needs a
teacher we don't have offline).

Token pooling (the paper) happens downstream of ``encode_docs`` — this
module never changes, exactly the paper's "no architectural change" claim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dt, init_dense
from repro.models.transformer import forward, init_transformer

# Special token ids (see data/tokenizer.py — shared vocabulary layout)
PAD_ID, CLS_ID, SEP_ID, MASK_ID, Q_MARK_ID, D_MARK_ID = 0, 1, 2, 3, 4, 5
N_SPECIAL = 8          # ids < N_SPECIAL are special
N_PUNCT = 16           # ids in [N_SPECIAL, N_SPECIAL + N_PUNCT) are punctuation


def init_colbert(key, cfg):
    """cfg: ColbertConfig. Returns {trunk, proj} param tree."""
    k1, k2 = jax.random.split(key)
    return {
        "trunk": init_transformer(k1, cfg.trunk),
        "proj": init_dense(k2, cfg.trunk.d_model, cfg.proj_dim,
                           dtype=dt(cfg.trunk.param_dtype)),
    }


def _encode(params, tokens, cfg, pad_mask):
    """tokens [B, L] -> unit vectors [B, L, proj_dim]. Its ops carry the
    scope ``encoder`` (``encoder/attention``, ``encoder/mlp`` per layer)
    in their metadata, for the device trace."""
    with jax.named_scope("encoder"):
        hidden = forward(params["trunk"], tokens, cfg.trunk,
                         pad_mask=pad_mask)
        v = dense(params["proj"], hidden).astype(jnp.float32)
        return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True),
                               1e-9)


def prepare_query_tokens(tokens, query_maxlen: int, cls_id: int = CLS_ID,
                         q_id: int = Q_MARK_ID, mask_id: int = MASK_ID):
    """[B, L] raw token ids -> ([B, Lq] with [CLS][Q]...[MASK] expansion,
    attention pad-mask (all True — MASK expansion tokens attend))."""
    B, L = tokens.shape
    body = tokens[:, :query_maxlen - 2]
    out = jnp.full((B, query_maxlen), mask_id, jnp.int32)
    out = out.at[:, 0].set(cls_id).at[:, 1].set(q_id)
    body_len = query_maxlen - 2
    pad = body_len - body.shape[1]
    body = jnp.pad(body, ((0, 0), (0, max(pad, 0))))[:, :body_len]
    # query augmentation: PAD slots become MASK (attended, vector-emitting)
    body = jnp.where(body == PAD_ID, mask_id, body)
    out = jax.lax.dynamic_update_slice(out, body.astype(jnp.int32), (0, 2))
    return out, jnp.ones((B, query_maxlen), bool)


def prepare_doc_tokens(tokens, doc_maxlen: int, cls_id: int = CLS_ID,
                       d_id: int = D_MARK_ID):
    """[B, L] raw ids -> ([B, Ld] with [CLS][D] prefix, pad mask)."""
    B, L = tokens.shape
    body = tokens[:, :doc_maxlen - 2]
    pad = (doc_maxlen - 2) - body.shape[1]
    body = jnp.pad(body, ((0, 0), (0, max(pad, 0))))
    out = jnp.concatenate(
        [jnp.full((B, 1), cls_id, jnp.int32),
         jnp.full((B, 1), d_id, jnp.int32),
         body.astype(jnp.int32)], axis=1)
    return out, out != PAD_ID


def emit_mask_docs(tokens, pad_mask, mask_punctuation: bool):
    """Which doc positions emit stored vectors: real, non-punct tokens
    (+ CLS/D markers, matching ColBERT's skiplist behaviour)."""
    m = pad_mask
    if mask_punctuation:
        punct = (tokens >= N_SPECIAL) & (tokens < N_SPECIAL + N_PUNCT)
        m = m & ~punct
    return m


@functools.partial(jax.jit, static_argnames=("cfg",))
def encode_queries(params, tokens, cfg):
    """Raw query token ids [B, L] -> ([B, Lq, dim] unit vectors, emit mask).

    Every expanded slot emits (ColBERT scores all Lq query vectors)."""
    toks, attn = prepare_query_tokens(tokens, cfg.query_maxlen, cfg.cls_id,
                                      cfg.q_marker_id, cfg.mask_id)
    v = _encode(params, toks, cfg, attn)
    return v, jnp.ones(toks.shape, bool)


@functools.partial(jax.jit, static_argnames=("cfg",))
def encode_docs(params, tokens, cfg):
    """Raw doc token ids [B, L] -> ([B, Ld, dim] unit vectors, emit mask)."""
    toks, attn = prepare_doc_tokens(tokens, cfg.doc_maxlen, cfg.cls_id,
                                    cfg.d_marker_id)
    v = _encode(params, toks, cfg, attn)
    emit = emit_mask_docs(toks, attn, cfg.mask_punctuation)
    return jnp.where(emit[..., None], v, 0.0), emit


# ---------------------------------------------------------------------------
# Training objective: in-batch-negative contrastive MaxSim
# ---------------------------------------------------------------------------
def colbert_loss(params, q_tokens, d_tokens, cfg):
    """q_tokens [B, Lq0], d_tokens [B, Ld0]; positives on the diagonal.

    Returns (loss, metrics). Uses full [B, B] in-batch score matrix.
    """
    qv, qm = encode_queries(params, q_tokens, cfg)
    dv, dm = encode_docs(params, d_tokens, cfg)
    # scores [B, B]: query i vs doc j
    sim = jnp.einsum("qld,nkd->qnlk", qv, dv)
    sim = jnp.where(dm[None, :, None, :], sim, -jnp.inf)
    best = jnp.max(sim, axis=-1)
    best = jnp.where(qm[:, None, :] & jnp.isfinite(best), best, 0.0)
    scores = jnp.sum(best, axis=-1)                    # [B, B]
    labels = jnp.arange(scores.shape[0])
    logp = jax.nn.log_softmax(scores, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
    acc = jnp.mean((jnp.argmax(scores, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}


def colbert_train_step(params, opt_state, q_tokens, d_tokens, cfg, opt):
    """One contrastive training step (used by examples/train_colbert.py)."""
    (loss, metrics), grads = jax.value_and_grad(
        colbert_loss, has_aux=True)(params, q_tokens, d_tokens, cfg)
    params, opt_state = opt.update(params, grads, opt_state)
    return params, opt_state, metrics
