"""Config dataclasses of the encoder: the bidirectional trunk and the
ColBERT head on top of it.

Configs are plain frozen dataclasses — hashable so they can be closed over by
jitted functions, serializable to dicts for checkpoints/manifests.
"""
from __future__ import annotations

from dataclasses import dataclass


# ---------------------------------------------------------------------------
# The bidirectional encoder trunk
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0                    # 0 -> d_model // n_heads

    # --- attention flavour ---
    causal: bool = False               # the encoder is bidirectional; only
                                       # False is accepted
    rope_theta: float = 1_000_000.0
    pos_emb: str = "rope"              # "rope" | "learned" | "none"
    attn_full_threshold: int = 2048    # padded inputs longer than this take
                                       # the masked kernel on the TPU
    # alternating local/global layers (ModernBERT): layer i is global when
    # i % global_every == 0, else local; a local layer attends only keys
    # with |i - j| <= local_window. 0 = every layer global.
    local_window: int = 0
    global_every: int = 1
    local_rope_theta: float = 0.0      # RoPE theta of local layers (0 ->
                                       # rope_theta)

    # --- mlp / norm ---
    gated_mlp: bool = True             # SwiGLU-style
    act: str = "silu"
    norm: str = "rmsnorm"              # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-6
    norm_bias: bool = True             # layernorm only: False = scale only
    embed_norm: bool = False           # a norm on the token embeddings
    first_attn_norm: bool = True       # False: layer 0 has no pre-attention
                                       # norm (identity)

    # --- execution ---
    max_seq_len: int = 32768
    dtype: str = "bfloat16"            # compute dtype
    param_dtype: str = "float32"
    remat: bool = True
    attn_shard: str = "heads"          # only "heads" is accepted

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        if self.causal:
            raise ValueError("the encoder trunk is bidirectional "
                             "(causal=False)")
        if self.attn_shard != "heads":
            raise ValueError(f"attn_shard={self.attn_shard!r}: only "
                             "'heads' is supported")

    def is_global(self, layer: int) -> bool:
        return not self.local_window or layer % self.global_every == 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


# ---------------------------------------------------------------------------
# ColBERT retrieval head on top of a TransformerConfig trunk
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ColbertConfig:
    name: str
    trunk: TransformerConfig
    proj_dim: int = 128
    doc_maxlen: int = 256
    query_maxlen: int = 32
    mask_punctuation: bool = True
    # [CLS], [Q] and [D] marker ids and the query-expansion [MASK] id;
    # raw token streams pad with 0, as everywhere in the system
    cls_id: int = 1
    mask_id: int = 3
    q_marker_id: int = 4
    d_marker_id: int = 5
    # Token pooling (the paper's technique) applied at indexing time:
    pool_method: str = "ward"          # "ward" | "kmeans" | "sequential" | "none"
    pool_factor: int = 1               # 1 = no pooling
    # Index backend
    index_backend: str = "plaid"       # "flat" | "hnsw" | "plaid"
    quant_bits: int = 2                # PLAID residual bits (2 or 4)
    n_centroids: int = 256             # IVF centroids
    nprobe: int = 8
    t_cs: float = 0.3                  # centroid score pruning threshold
    ndocs: int = 8192                  # candidate docs fed to decompression
    maxsim_impl: str = "einsum"        # "einsum" | "blocked" (serving path)
    maxsim_block: int = 512            # docs per block in the blocked path
