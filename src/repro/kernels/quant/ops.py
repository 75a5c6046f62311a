"""jit'd wrapper: fused dequantize+score for PLAID candidate reranking."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.quant.kernel import dequant_score_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("bits", "block_m"))
def dequant_score(words, centroid_ids, centroids, values, q, *,
                  bits: int = 2, block_m: int = 256):
    """Fused candidate scoring.

    words [M, W] packed codes; centroid_ids [M] int32; centroids [K, dim];
    values [dim, 2^b]; q [Lq, dim]. Returns sims [M, Lq] f32.

    The centroid row gather happens outside the kernel (one take, cheap);
    unpack + reconstruct + normalize + score fuse inside.
    """
    rows = jnp.take(centroids, centroid_ids, axis=0)
    M = words.shape[0]
    pad = (-M) % block_m
    words = jax.lax.bitcast_convert_type(words.astype(jnp.uint32), jnp.int32)
    words_t = jnp.pad(words, ((0, pad), (0, 0))).T
    rows_t = jnp.pad(rows.astype(jnp.float32), ((0, pad), (0, 0))).T
    out = dequant_score_pallas(words_t, rows_t, values, q, bits=bits,
                               block_m=block_m, interpret=not _on_tpu())
    return out[:, :M].T
