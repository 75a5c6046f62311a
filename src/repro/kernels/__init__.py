"""Pallas TPU kernels for the compute hot-spots of the retrieval stack.

Each kernel ships kernel.py (pl.pallas_call + BlockSpec tiling), ops.py
(jit'd public wrapper, interpret=True off-TPU) and ref.py (pure-jnp
oracle the tests sweep shapes/dtypes against). The encoder's masked
attention is one file, ``flash_attention/masked.py``: its oracle is the
encoder's own dense path, ``models.attention._full_attn``.
"""
from repro.kernels.maxsim.ops import maxsim
from repro.kernels.maxsim_packed.ops import maxsim_packed_rerank
from repro.kernels.kmeans_assign.ops import kmeans_assign
from repro.kernels.quant.ops import dequant_score

__all__ = ["maxsim", "maxsim_packed_rerank", "kmeans_assign",
           "dequant_score"]
