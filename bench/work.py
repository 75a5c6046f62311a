"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick for every roofline share and ``mfu`` the
benchmark reports. They count what the algorithm requires, not what an
implementation happens to do: MaxSim is ``2 * Lq * dim`` FLOP per
(query token, real doc token) pair scored; packed codes, the centroid
table and the queries are each read once; the one-hot decode and gather
matmuls some kernels use are not counted. A kernel that drops such a
trick is then measured against the same work.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` raises."""
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add it with its source")
    return table[device_kind]


def encoder_params(n_layers: int, d_model: int, d_ff: int,
                   proj_dim: int) -> int:
    """Matmul weights a token passes through: per layer the four
    attention projections and the two MLP matrices, then the ColBERT
    projection (embedding lookups and norms are not FLOPs)."""
    per_layer = 4 * d_model * d_model + 2 * d_model * d_ff
    return n_layers * per_layer + d_model * proj_dim


def encoder_flops(n_layers: int, d_model: int, d_ff: int, proj_dim: int,
                  seq_lens: Iterable[int]) -> float:
    """Forward FLOPs over sequences of the given real lengths: 2 per
    weight per token, plus attention scores and the weighted sum (2 * L
    * d_model each, per token per layer) over the real tokens."""
    p = encoder_params(n_layers, d_model, d_ff, proj_dim)
    total = 0.0
    for L in seq_lens:
        total += 2.0 * p * L + n_layers * 4.0 * L * L * d_model
    return total


def maxsim_flops(lq: int, dim: int, doc_tokens: float) -> float:
    """One query of ``lq`` tokens against ``doc_tokens`` real doc
    tokens: a dot product per (query token, doc token) pair."""
    return 2.0 * lq * dim * doc_tokens


def centroid_score_flops(nq: int, lq: int, n_centroids: int,
                         dim: int) -> float:
    return 2.0 * nq * lq * n_centroids * dim


def ward_gram_flops(n_tokens: int, dim: int) -> float:
    """The Gram matrix Ward's initial distances need for one doc."""
    return 2.0 * n_tokens * n_tokens * dim


def codec_assign_flops(n_vectors: float, n_centroids: int,
                       dim: int) -> float:
    """Nearest-centroid scores of the stored vectors."""
    return 2.0 * n_vectors * n_centroids * dim


def packed_bytes_per_vector(dim: int, bits: int) -> float:
    """A stored vector: its centroid id (4 B) and packed residual."""
    return 4.0 + dim * bits / 8.0


def packed_rerank_work(nq: int, lq: int, dim: int, bits: int,
                       n_centroids: int, doc_tokens: float) -> tuple:
    """(FLOP, bytes) of scoring ``nq`` queries against ``doc_tokens``
    stored vectors in all (the batch's reranked docs' real tokens)."""
    flops = maxsim_flops(lq, dim, doc_tokens)
    nbytes = (doc_tokens * packed_bytes_per_vector(dim, bits)
              + n_centroids * dim * 4 + dim * (1 << bits) * 4
              + nq * lq * dim * 4)
    return flops, nbytes


def probe_work(nq: int, lq: int, dim: int, n_centroids: int,
               cand_tokens: float, stored_tokens: float) -> tuple:
    """(FLOP, bytes) of PLAID's centroid interaction for a batch: the
    query-centroid scores, then a max and a sum per (query token,
    candidate token) — ``cand_tokens`` summed over the batch's queries —
    reading each stored centroid id once."""
    flops = (centroid_score_flops(nq, lq, n_centroids, dim)
             + 2.0 * lq * cand_tokens)
    nbytes = stored_tokens * 4 + n_centroids * dim * 4 + nq * lq * dim * 4
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(share of the roofline in %, bound): the least time the chip
    could take — the larger of FLOP over peak FLOP/s and bytes over
    peak bytes/s — over the measured time."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
