"""Plain reference for GTE-ModernColBERT: the ModernBERT encoder, its
ColBERT head, Ward pooling and MaxSim.

Written from the published descriptions (ModernBERT, arXiv:2412.13663;
lightonai/GTE-ModernColBERT-v1) and imports nothing of the program under
test: float32 jax.numpy at HIGHEST matmul precision with dense [S, S]
scores and an explicit band mask, no kernels. Per layer i:

* ``h = x`` on layer 0, else a bias-free LayerNorm of x (eps 1e-5);
* q, k, v = h Wq, h Wk, h Wv (no biases), split into heads of 64; RoPE
  (rotate-half) at theta 160000 on global layers (``i % 3 == 0``) and
  10000 on local ones; softmax(q k^T / 8) over valid keys, and on local
  layers only keys with |i - j| <= 64; ``x += o Wo``;
* GeGLU: ``[a | g] = LN(x) Wi`` with ``Wi = [w1 | w3]`` (768 -> 2 x
  1152: the first half is the input, the second the gate), ``x +=
  (gelu_erf(a) * g) W2``.

Token embeddings take a bias-free LayerNorm first; no position
embedding; a final LayerNorm; then the bias-free 768 -> 128 projection
and L2 normalization. Departures kept from the system (PERF.md, the
configuration's ``assumed``): ColBERT's [CLS][Q]/[D] marker convention
with the configuration's ids, queries padded to ``query_maxlen`` with
[MASK] tokens that attend and emit, doc padding (raw id 0) masked out
of attention and of the stored set, and the synthetic skiplist ids
[8, 24) masked out of doc vectors. Ward clustering and MaxSim run in
float64 numpy.

``cast`` rounds every matmul operand (e.g. to float8) to build the
lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0
PUNCT_LO, PUNCT_HI = 8, 24        # skiplist ids, masked out of doc vectors
DOC_CHUNK = 4                     # docs per reference call: the dense
#                                   [4, 12, 2048, 2048] f32 scores are 805 MB


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"]


def _rope(x, theta):
    """x [B, S, H, dh], rotate-half convention."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("tr", "cast"))
def _forward(params, toks, attn_mask, *, tr, cast):
    """toks [B, S] -> unit vectors [B, S, proj] in float32. ``tr`` is
    the trunk's (key, value) pairs."""
    tr = dict(tr)
    hi = jax.lax.Precision.HIGHEST
    c = (lambda a: a) if cast is None else (
        lambda a: a.astype(cast).astype(jnp.float32))

    def mm(a, b):
        return jnp.matmul(c(a), c(b), precision=hi)

    t = params["trunk"]
    eps, H = float(tr["norm_eps"]), int(tr["n_heads"])
    B, S = toks.shape
    x = _ln(t["embed"]["table"][toks], t["embed_norm"], eps)
    L = t["dense_layers"]
    d = x.shape[-1]
    dh = d // H
    pos = np.arange(S)
    band = np.abs(pos[:, None] - pos[None, :]) <= int(tr["local_window"])
    key_ok = attn_mask[:, None, None, :]
    for i in range(int(tr["n_layers"])):
        lp = jax.tree_util.tree_map(lambda a: a[i], L)
        glob = i % int(tr["global_every"]) == 0
        h = x if i == 0 else _ln(x, lp["attn_norm"], eps)
        theta = float(tr["rope_theta"] if glob else tr["local_rope_theta"])
        q = _rope(mm(h, lp["attn"]["wq"]["w"]).reshape(B, S, H, dh), theta)
        k = _rope(mm(h, lp["attn"]["wk"]["w"]).reshape(B, S, H, dh), theta)
        v = mm(h, lp["attn"]["wv"]["w"]).reshape(B, S, H, dh)
        s = jnp.einsum("bqhd,bkhd->bhqk", c(q), c(k), precision=hi)
        ok = key_ok if glob else key_ok & band[None, None]
        w = jax.nn.softmax(jnp.where(ok, s / np.sqrt(dh), -jnp.inf), -1)
        w = jnp.where(jnp.isnan(w), 0.0, w)      # rows with no key: padding
        o = jnp.einsum("bhqk,bkhd->bqhd", c(w), c(v), precision=hi)
        x = x + mm(o.reshape(B, S, d), lp["attn"]["wo"]["w"])
        h = _ln(x, lp["mlp_norm"], eps)
        wi = jnp.concatenate([lp["mlp"]["w1"]["w"], lp["mlp"]["w3"]["w"]], 1)
        a, g = jnp.split(mm(h, wi), 2, axis=-1)
        x = x + mm(jax.nn.gelu(a, approximate=False) * g,
                   lp["mlp"]["w2"]["w"])
    x = _ln(x, t["final_norm"], eps)
    v = mm(x, params["proj"]["w"])
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-9)


def _trunk(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model["trunk"].items()
                        if isinstance(v, (int, float, str))))


def query_input(tokens: np.ndarray, model: dict) -> np.ndarray:
    """[CLS][Q] + body, padded with [MASK] to ``query_maxlen``."""
    Lq = int(model["query_maxlen"])
    out = np.full((len(tokens), Lq), int(model["mask_id"]), np.int32)
    out[:, 0], out[:, 1] = int(model["cls_id"]), int(model["q_marker_id"])
    body = np.asarray(tokens)[:, :Lq - 2]
    out[:, 2:2 + body.shape[1]] = np.where(body == PAD,
                                           int(model["mask_id"]), body)
    return out


def doc_input(tokens: np.ndarray, model: dict) -> tuple:
    """([CLS][D] + body, padded with 0 to ``doc_maxlen``; the mask of
    the positions that emit a stored vector)."""
    Ld = int(model["doc_maxlen"])
    out = np.zeros((len(tokens), Ld), np.int32)
    out[:, 0], out[:, 1] = int(model["cls_id"]), int(model["d_marker_id"])
    body = np.asarray(tokens)[:, :Ld - 2]
    out[:, 2:2 + body.shape[1]] = body
    emit = (out != PAD) & ~((out >= PUNCT_LO) & (out < PUNCT_HI))
    return out, emit


def encode_queries(params, model: dict, tokens, cast=None) -> np.ndarray:
    """[B, L] raw ids -> [B, query_maxlen, proj] float32 unit vectors."""
    toks = query_input(tokens, model)
    return np.asarray(_forward(params, jnp.asarray(toks),
                               jnp.ones(toks.shape, bool),
                               tr=_trunk(model), cast=cast))


def encode_docs(params, model: dict, tokens, cast=None) -> list:
    """[B, L] raw ids -> per doc its emitted vectors [n_i, proj], in
    calls of ``DOC_CHUNK`` docs."""
    toks, emit = doc_input(tokens, model)
    out = []
    for lo in range(0, len(toks), DOC_CHUNK):
        part = toks[lo:lo + DOC_CHUNK]
        # one shape for every call: pad with the chunk's first doc
        fill = np.repeat(part[:1], DOC_CHUNK - len(part), axis=0)
        v = np.asarray(_forward(
            params, jnp.asarray(np.concatenate([part, fill])),
            jnp.asarray(np.concatenate([part, fill]) != PAD),
            tr=_trunk(model), cast=cast))
        out += [v[r][emit[lo + r]] for r in range(len(part))]
    return out


def ward(x: np.ndarray, factor: int) -> np.ndarray:
    """Ward agglomerative clustering of one doc's vectors (cosine, i.e.
    on unit vectors) down to ``n // factor + 1`` clusters; returns the
    cluster means, renormalized, ordered by each cluster's first token.

    The closest pair (smallest Ward distance, first in row-major order
    on ties) merges first; distances update by Lance-Williams. Each
    row's minimum and its first column are kept, so a merge costs O(n)
    and not the O(n^2) of a scan of the whole matrix: the first row
    holding the least row minimum, at its first column, is the
    row-major argmin."""
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
    rmin, rarg = d2.min(1), d2.argmin(1)
    for _ in range(n - k):
        i = int(np.argmin(rmin))
        i, j = min(i, int(rarg[i])), max(i, int(rarg[i]))
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
        # rows whose minimum sat at column i or j: rescan; the others
        # only gain the new value at column i
        stale = (rarg == i) | (rarg == j)
        stale[[i, j]] = True
        gain = ~stale & ((row < rmin) | ((row == rmin) & (i < rarg)))
        rmin[gain], rarg[gain] = row[gain], i
        for r in np.flatnonzero(stale):
            rmin[r], rarg[r] = d2[r].min(), d2[r].argmin()
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
