"""Operations an encoder with banded local layers needs (ModernBERT's
alternating pattern), from shapes alone: the yardstick of the long-doc
cell's attention roofline and ``mfu``.

Per doc of ``L`` valid tokens (a prefix of the row; padding neither
queries nor is attended): a global layer scores every valid (query,
key) pair, a local layer only the pairs with |i - j| <= window; each
scored pair costs ``4 * d_model`` FLOP (its score and its share of the
weighted sum, 2 * d_model each). Linears cost 2 per weight per valid
token: four attention projections and GeGLU's three matrices a layer,
then the ColBERT projection.
"""
from __future__ import annotations

from typing import Iterable


def band_pairs(L: int, window: int) -> int:
    """(query, key) pairs with |i - j| <= window among L tokens."""
    w = min(window, max(L - 1, 0))
    return L * (2 * w + 1) - w * (w + 1)


def is_global(layer: int, global_every: int) -> bool:
    return layer % global_every == 0


def attention_flops(trunk: dict, lens: Iterable[int]) -> float:
    """Scores and weighted sums of every layer over docs of these valid
    lengths: all valid pairs on global layers, the band on local ones."""
    n, d = int(trunk["n_layers"]), int(trunk["d_model"])
    every, w = int(trunk["global_every"]), int(trunk["local_window"])
    n_glob = sum(is_global(i, every) for i in range(n))
    total = 0.0
    for L in lens:
        total += 4.0 * d * (n_glob * L * L
                            + (n - n_glob) * band_pairs(int(L), w))
    return total


def attention_bytes(trunk: dict, lens: Iterable[int]) -> float:
    """q, k, v read and the output written once per layer, in bf16."""
    return 2.0 * 4 * int(trunk["n_layers"]) * int(trunk["d_model"]) \
        * float(sum(lens))


def linear_params(trunk: dict, proj_dim: int) -> int:
    d, ff = int(trunk["d_model"]), int(trunk["d_ff"])
    return int(trunk["n_layers"]) * (4 * d * d + 3 * d * ff) + d * proj_dim


def encoder_flops(model: dict, lens) -> float:
    """Forward FLOPs over docs of these valid lengths."""
    tr = model["trunk"]
    lens = [int(L) for L in lens]
    return (2.0 * linear_params(tr, int(model["proj_dim"])) * sum(lens)
            + attention_flops(tr, lens))
