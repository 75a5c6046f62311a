"""Plain reference for the ColBERT encoder, Ward pooling and MaxSim.

Written from the published descriptions and imports nothing of the
program under test: a BERT-base-shaped encoder in float32 at HIGHEST
matmul precision (jax.numpy, no kernels, no batching tricks), Ward
agglomerative clustering in float64 numpy, exact MaxSim in float64.

It runs the encoder as the program's configuration states it, which
departs from the original BERT in three places (listed in PERF.md):
pre-LayerNorm blocks with a final LayerNorm (BERT is post-LN with an
embedding LayerNorm), no token-type embedding, and GELU in its tanh
form. ColBERT's head follows the paper: [CLS] then a [Q]/[D] marker,
queries padded to ``query_maxlen`` with [MASK] tokens that attend and
emit, doc padding masked out of attention and out of the stored set,
a linear projection to ``proj_dim`` and L2 normalization.

``cast`` rounds every matmul operand (e.g. to float8) to build the
lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD, CLS, MASK, QMARK, DMARK = 0, 1, 3, 4, 5
PUNCT_LO, PUNCT_HI = 8, 24        # skiplist ids, masked out of doc vectors


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi)
                                   * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "cast"))
def _forward(params, toks, attn_mask, *, n_heads, eps, cast):
    """toks [B, S] -> unit vectors [B, S, proj] in float32."""
    hi = jax.lax.Precision.HIGHEST
    c = (lambda a: a) if cast is None else (
        lambda a: a.astype(cast).astype(jnp.float32))

    def mm(a, b):
        return jnp.matmul(c(a), c(b), precision=hi)

    t = params["trunk"]
    B, S = toks.shape
    x = t["embed"]["table"][toks] + t["pos_embed"]["table"][:S][None]
    L = t["dense_layers"]
    n_layers = L["attn"]["wq"]["w"].shape[0]
    d = x.shape[-1]
    dh = d // n_heads
    neg = jnp.where(attn_mask, 0.0, -jnp.inf)[:, None, None, :]
    for i in range(n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], L)
        h = _ln(x, lp["attn_norm"], eps)
        q = mm(h, lp["attn"]["wq"]["w"]).reshape(B, S, n_heads, dh)
        k = mm(h, lp["attn"]["wk"]["w"]).reshape(B, S, n_heads, dh)
        v = mm(h, lp["attn"]["wv"]["w"]).reshape(B, S, n_heads, dh)
        s = jnp.einsum("bqhd,bkhd->bhqk", c(q), c(k), precision=hi)
        w = jax.nn.softmax(s / np.sqrt(dh) + neg, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", c(w), c(v), precision=hi)
        x = x + mm(o.reshape(B, S, d), lp["attn"]["wo"]["w"])
        h = _ln(x, lp["mlp_norm"], eps)
        x = x + mm(_gelu(mm(h, lp["mlp"]["w1"]["w"])), lp["mlp"]["w2"]["w"])
    x = _ln(x, t["final_norm"], eps)
    v = mm(x, params["proj"]["w"])
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-9)


def query_input(tokens: np.ndarray, query_maxlen: int) -> np.ndarray:
    """[CLS][Q] + body, padded with [MASK] to ``query_maxlen``."""
    B = len(tokens)
    out = np.full((B, query_maxlen), MASK, np.int32)
    out[:, 0], out[:, 1] = CLS, QMARK
    body = np.asarray(tokens)[:, :query_maxlen - 2]
    body = np.where(body == PAD, MASK, body)
    out[:, 2:2 + body.shape[1]] = body
    return out


def doc_input(tokens: np.ndarray, doc_maxlen: int) -> tuple:
    """([CLS][D] + body, padded with [PAD] to ``doc_maxlen``; the mask
    of the positions that emit a stored vector)."""
    B = len(tokens)
    out = np.zeros((B, doc_maxlen), np.int32)
    out[:, 0], out[:, 1] = CLS, DMARK
    body = np.asarray(tokens)[:, :doc_maxlen - 2]
    out[:, 2:2 + body.shape[1]] = body
    emit = (out != PAD) & ~((out >= PUNCT_LO) & (out < PUNCT_HI))
    return out, emit


def encode_queries(params, model: dict, tokens, cast=None) -> np.ndarray:
    """[B, L] raw ids -> [B, query_maxlen, proj] float32 unit vectors."""
    toks = query_input(tokens, int(model["query_maxlen"]))
    tr = model["trunk"]
    v = _forward(params, jnp.asarray(toks), jnp.ones(toks.shape, bool),
                 n_heads=int(tr["n_heads"]), eps=float(tr["norm_eps"]),
                 cast=cast)
    return np.asarray(v)


def encode_docs(params, model: dict, tokens, cast=None) -> list:
    """[B, L] raw ids -> per doc its emitted vectors [n_i, proj]."""
    toks, emit = doc_input(tokens, int(model["doc_maxlen"]))
    tr = model["trunk"]
    v = np.asarray(_forward(params, jnp.asarray(toks),
                            jnp.asarray(toks != PAD),
                            n_heads=int(tr["n_heads"]),
                            eps=float(tr["norm_eps"]), cast=cast))
    return [v[i][emit[i]] for i in range(len(v))]


def ward(x: np.ndarray, factor: int) -> np.ndarray:
    """Ward agglomerative clustering of one doc's vectors (cosine, i.e.
    on unit vectors) down to ``n // factor + 1`` clusters; returns the
    cluster means, renormalized, ordered by each cluster's first token.

    The closest pair (smallest Ward distance, first in row-major order
    on ties) merges first; distances update by Lance-Williams."""
    x = np.asarray(x, np.float64)
    n = len(x)
    k = n // factor + 1
    if n <= k:
        return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True),
                              1e-9)
    u = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)
    sq = (u * u).sum(1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2 * u @ u.T, 0.0)
    np.fill_diagonal(d2, np.inf)
    size = np.ones(n)
    rep = np.arange(n)
    for _ in range(n - k):
        i, j = divmod(int(np.argmin(d2)), n)
        i, j = min(i, j), max(i, j)
        si, sj = size[i], size[j]
        row = ((si + size) * d2[i] + (sj + size) * d2[j]
               - size * d2[i, j]) / (si + sj + size)
        row[np.isinf(d2[i]) | np.isinf(d2[j])] = np.inf
        row[i] = row[j] = np.inf
        d2[i, :] = d2[:, i] = row
        d2[j, :] = d2[:, j] = np.inf
        size[i] += sj
        size[j] = 0
        rep[rep == j] = i
    out = []
    for r in np.unique(rep):
        m = x[rep == r].mean(0)
        out.append(m / max(np.linalg.norm(m), 1e-9))
    return np.stack(out)


def maxsim(q: np.ndarray, d: np.ndarray) -> float:
    """Exact float64 late-interaction score of query tokens q [Lq, dim]
    against doc vectors d [n, dim]."""
    s = np.asarray(q, np.float64) @ np.asarray(d, np.float64).T
    return float(s.max(axis=1).sum())
