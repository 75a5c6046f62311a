"""PLAID/ColBERTv2-style residual quantization (paper §3.1 "2-bit
quantization ... performed with the original codebase").

Every token vector v is stored as:
    centroid id  (int32 -> the IVF coarse quantizer)
  + per-dimension b-bit bucket code of the residual r = v - c[id]

Bucket cutoffs are residual quantiles (2^b buckets per dimension), bucket
reconstruction values are the per-bucket means — matching the ColBERTv2
codec. Codes are bit-packed, 16 codes per int32 word at b=2.

All encode/decode paths are jnp (jit-able, shardable); the fused
dequant+score Pallas kernel lives in kernels/quant.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant.ref import unpack_ref


@dataclass
class ResidualCodec:
    centroids: jnp.ndarray      # [K, dim] unit vectors
    cutoffs: jnp.ndarray        # [dim, 2^b - 1] bucket boundaries
    values: jnp.ndarray         # [dim, 2^b] reconstruction values
    bits: int

    @property
    def dim(self):
        return self.centroids.shape[1]

    @property
    def n_centroids(self):
        return self.centroids.shape[0]


def train_codec(vectors, centroids, bits: int = 2,
                sample: int = 65536, seed: int = 0) -> ResidualCodec:
    """Fit bucket cutoffs/values from (a sample of) residuals.

    vectors: [M, dim]; centroids: [K, dim].
    """
    vectors = jnp.asarray(vectors, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    M = vectors.shape[0]
    if M > sample:
        idx = jax.random.permutation(jax.random.PRNGKey(seed), M)[:sample]
        vectors = vectors[idx]
    assign = jnp.argmax(vectors @ centroids.T, axis=-1)
    res = vectors - centroids[assign]                       # [m, dim]
    nb = 1 << bits
    qs = jnp.arange(1, nb) / nb                             # 2^b - 1 quantiles
    cutoffs = jnp.quantile(res, qs, axis=0).T               # [dim, nb-1]
    # bucket values = mean of residuals falling in the bucket
    codes = _bucketize(res, cutoffs)                        # [m, dim]
    dim = res.shape[1]
    flat_seg = codes + (jnp.arange(dim)[None, :] * nb)
    sums = jax.ops.segment_sum(res.T.reshape(-1),
                               flat_seg.T.reshape(-1),
                               num_segments=dim * nb)
    cnts = jax.ops.segment_sum(jnp.ones_like(res.T.reshape(-1)),
                               flat_seg.T.reshape(-1),
                               num_segments=dim * nb)
    values = (sums / jnp.maximum(cnts, 1.0)).reshape(dim, nb)
    return ResidualCodec(centroids=centroids, cutoffs=cutoffs,
                         values=values, bits=bits)


def _bucketize(res, cutoffs):
    """res: [M, dim]; cutoffs: [dim, nb-1] -> codes [M, dim] int32."""
    # code = number of cutoffs strictly below the value
    return jnp.sum(res[:, :, None] > cutoffs[None, :, :], axis=-1) \
        .astype(jnp.int32)


# ---------------------------------------------------------------------------
# Bit packing: codes [M, dim] (b bits each) <-> words [M, dim*b/32] int32
# ---------------------------------------------------------------------------
def _codes_per_word(bits):
    assert 32 % bits == 0
    return 32 // bits


@functools.partial(jax.jit, static_argnames=("bits",))
def pack_codes(codes, bits: int):
    # the codec's one jitted step: its ops carry the scope ``codec`` in
    # the device trace (a scope labels only ops traced inside a jit, so
    # ``encode``'s eager steps carry none)
    with jax.named_scope("codec"):
        M, dim = codes.shape
        cpw = _codes_per_word(bits)
        assert dim % cpw == 0, (dim, cpw)
        c = codes.reshape(M, dim // cpw, cpw).astype(jnp.uint32)
        shifts = (jnp.arange(cpw, dtype=jnp.uint32) * bits)
        words = jnp.sum(c << shifts[None, None, :], axis=-1)
        return words.astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bits", "dim"))
def unpack_codes(words, bits: int, dim: int):
    return unpack_ref(words, bits, dim)


# ---------------------------------------------------------------------------
# Encode / decode
# ---------------------------------------------------------------------------
def encode(codec: ResidualCodec, vectors):
    """vectors [M, dim] -> (centroid ids [M], packed words [M, W])."""
    vectors = jnp.asarray(vectors, jnp.float32)
    assign = jnp.argmax(vectors @ codec.centroids.T, axis=-1).astype(jnp.int32)
    res = vectors - codec.centroids[assign]
    codes = _bucketize(res, codec.cutoffs)
    return assign, pack_codes(codes, codec.bits)


def decode(codec: ResidualCodec, assign, words):
    """-> reconstructed vectors [M, dim] (unit-renormalized)."""
    dim = codec.dim
    codes = unpack_codes(words, codec.bits, dim)       # [M, dim]
    res = codec.values[jnp.arange(dim)[None, :], codes]  # [M, dim]
    v = codec.centroids[assign] + res
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-9)


def reconstruction_error(codec: ResidualCodec, vectors):
    a, w = encode(codec, vectors)
    rec = decode(codec, a, w)
    vn = vectors / jnp.maximum(
        jnp.linalg.norm(vectors, axis=-1, keepdims=True), 1e-9)
    return jnp.mean(jnp.sum(vn * rec, axis=-1))        # mean cosine


def storage_bytes(n_vectors: int, dim: int, bits: int) -> int:
    """Bytes for the compressed store: ids (4B) + packed codes."""
    return n_vectors * (4 + dim * bits // 8)
