"""Config dataclasses for every architecture family in the framework.

Configs are plain frozen dataclasses — hashable so they can be closed over by
jitted functions, serializable to dicts for checkpoints/manifests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple


def asdict(cfg) -> dict:
    return dataclasses.asdict(cfg)


# ---------------------------------------------------------------------------
# LM transformers (dense + MoE) — also the ColBERT encoder trunk
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

    # --- MoE ---
    moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                  # per-expert FFN width (d_ff if 0)
    n_shared_experts: int = 0
    first_dense_layers: int = 0        # leading dense layers before MoE stack
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01      # load-balance loss coefficient
    moe_impl: str = "capacity"         # "capacity" | "ep" (shard_map
                                       # all-to-all) | "dense" (oracle)

    # --- attention flavour ---
    causal: bool = True
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1_000_000.0
    pos_emb: str = "rope"              # "rope" | "learned" | "none"
    attn_chunk: int = 1024             # kv/q chunk for online-softmax attention
    attn_full_threshold: int = 2048    # use plain attention below this seq len
    # alternating local/global layers (ModernBERT): layer i is global when
    # i % global_every == 0, else local; a local layer attends only keys
    # with |i - j| <= local_window. 0 = every layer global.
    local_window: int = 0
    global_every: int = 1
    local_rope_theta: float = 0.0      # RoPE theta of local layers (0 ->
                                       # rope_theta)
    use_flash_kernel: bool = False     # dispatch the Pallas kernel (TPU;
                                       # interpret=True on CPU — slow, tests only)

    # --- mlp / norm ---
    gated_mlp: bool = True             # SwiGLU-style
    act: str = "silu"
    norm: str = "rmsnorm"              # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-6
    norm_bias: bool = True             # layernorm only: False = scale only
    embed_norm: bool = False           # a norm on the token embeddings
    first_attn_norm: bool = True       # False: layer 0 has no pre-attention
                                       # norm (identity)
    tie_embeddings: bool = False

    # --- execution ---
    max_seq_len: int = 32768
    dtype: str = "bfloat16"            # compute dtype
    param_dtype: str = "float32"
    scan_layers: bool = True
    remat: bool = True
    logits_chunk: int = 1024           # seq-chunking of the xent loss

    # --- sharding hints ---
    attn_shard: str = "heads"          # "heads" | "sequence" (when H % tp != 0)
    optimizer: str = "adamw"           # "adamw" | "adafactor"
    fsdp_params: bool = True           # ZeRO-3: shard weights on data axis too
    train_microbatches: int = 1        # grad-accumulation inside train_step
    grad_accum_dtype: str = "float32"  # bf16 halves the accumulator for 1T
    # Dry-run analysis mode: fully unroll lax.scan loops so XLA
    # cost_analysis counts every iteration (while-loop bodies are otherwise
    # counted ONCE — roofline flops would be ~L x under-reported).
    unroll_scans: bool = False

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        if self.moe and self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)
        if self.local_window and (self.moe or self.causal):
            raise ValueError("local/global layers are a dense "
                             "bidirectional encoder's")

    def is_global(self, layer: int) -> bool:
        return not self.local_window or layer % self.global_every == 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def param_count(self) -> int:
        """Analytic parameter count (used by roofline MODEL_FLOPS)."""
        d, dh, H, KV = self.d_model, self.d_head, self.n_heads, self.n_kv_heads
        attn = d * (H * dh) * 2 + d * (KV * dh) * 2          # q,o + k,v
        if self.qkv_bias:
            attn += (H + 2 * KV) * dh
        dense_ffn = d * self.d_ff * (3 if self.gated_mlp else 2)
        n_moe = max(self.n_layers - self.first_dense_layers, 0) if self.moe else 0
        n_dense = self.n_layers - n_moe
        total = n_dense * (attn + dense_ffn)
        if self.moe:
            expert = d * self.moe_d_ff * (3 if self.gated_mlp else 2)
            router = d * self.n_experts
            shared = self.n_shared_experts * expert
            total += n_moe * (attn + self.n_experts * expert + router + shared)
        total += 2 * self.n_layers * d                        # norms
        total += self.vocab_size * d                          # embed
        if not self.tie_embeddings:
            total += self.vocab_size * d                      # lm head
        if self.pos_emb == "learned":
            total += self.max_seq_len * d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top_k + shared only)."""
        if not self.moe:
            return self.param_count()
        d = self.d_model
        expert = d * self.moe_d_ff * (3 if self.gated_mlp else 2)
        n_moe = max(self.n_layers - self.first_dense_layers, 0)
        inactive = n_moe * (self.n_experts - self.top_k) * expert
        return self.param_count() - inactive


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


# ---------------------------------------------------------------------------
# GNN — DimeNet
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DimeNetConfig:
    name: str
    n_blocks: int = 6
    d_hidden: int = 128
    n_bilinear: int = 8
    n_spherical: int = 7
    n_radial: int = 6
    d_feat_in: int = 0                 # input node feature dim (0 = atom types)
    n_targets: int = 1
    cutoff: float = 5.0
    envelope_exponent: int = 5
    n_atom_types: int = 95
    # triplet budget per edge (TPU fixed shapes): n_triplets = n_edges * triplet_cap
    triplet_cap: int = 8
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    optimizer: str = "adamw"
    unroll_scans: bool = False         # analysis mode (see TransformerConfig)


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str                          # "wide_deep" | "deepfm" | "fm" | "dlrm"
    n_sparse: int
    embed_dim: int
    n_dense: int = 0
    vocab_sizes: Tuple[int, ...] = ()  # per-field table rows; filled by configs
    mlp_dims: Tuple[int, ...] = ()
    bot_mlp_dims: Tuple[int, ...] = ()
    top_mlp_dims: Tuple[int, ...] = ()
    interaction: str = "dot"           # "dot" | "fm" | "fm-2way" | "concat"
    multi_hot: int = 1                 # ids per sparse field (EmbeddingBag bag size)
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    optimizer: str = "adamw"

    def __post_init__(self):
        if not self.vocab_sizes:
            object.__setattr__(
                self, "vocab_sizes", tuple([1_000_000] * self.n_sparse)
            )
        assert len(self.vocab_sizes) == self.n_sparse


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeCell:
    """One (input-shape x step-kind) cell of the dry-run matrix."""
    name: str
    kind: str                          # train | prefill | decode | serve | ...
    dims: Tuple[Tuple[str, int], ...]  # ordered (name, value) pairs

    def dim(self, key: str) -> int:
        for k, v in self.dims:
            if k == key:
                return v
        raise KeyError(key)

    def get(self, key: str, default=None):
        for k, v in self.dims:
            if k == key:
                return v
        return default


LM_SHAPES = (
    ShapeCell("train_4k", "train", (("seq_len", 4096), ("global_batch", 256))),
    ShapeCell("prefill_32k", "prefill", (("seq_len", 32768), ("global_batch", 32))),
    ShapeCell("decode_32k", "decode", (("seq_len", 32768), ("global_batch", 128))),
    ShapeCell("long_500k", "decode", (("seq_len", 524288), ("global_batch", 1))),
)

GNN_SHAPES = (
    ShapeCell("full_graph_sm", "train",
              (("n_nodes", 2708), ("n_edges", 10556), ("d_feat", 1433))),
    ShapeCell("minibatch_lg", "train",
              (("n_nodes", 232965), ("n_edges", 114615892),
               ("batch_nodes", 1024), ("fanout0", 15), ("fanout1", 10))),
    ShapeCell("ogb_products", "train",
              (("n_nodes", 2449029), ("n_edges", 61859140), ("d_feat", 100))),
    ShapeCell("molecule", "train",
              (("n_nodes", 30), ("n_edges", 64), ("batch", 128))),
)

RECSYS_SHAPES = (
    ShapeCell("train_batch", "train", (("batch", 65536),)),
    ShapeCell("serve_p99", "serve", (("batch", 512),)),
    ShapeCell("serve_bulk", "serve", (("batch", 262144),)),
    ShapeCell("retrieval_cand", "serve", (("batch", 1), ("n_candidates", 1_000_000))),
)

# ColBERT's own (extra, beyond the 40 assigned cells)
COLBERT_SHAPES = (
    ShapeCell("index_build", "index", (("n_docs", 4096), ("doc_len", 256))),
    ShapeCell("search", "search",
              (("n_queries", 64), ("query_len", 32),
               ("n_docs", 65536), ("doc_len", 256))),
)


def shapes_for(cfg) -> Tuple[ShapeCell, ...]:
    if isinstance(cfg, TransformerConfig):
        return LM_SHAPES
    if isinstance(cfg, DimeNetConfig):
        return GNN_SHAPES
    if isinstance(cfg, RecsysConfig):
        return RECSYS_SHAPES
    if isinstance(cfg, ColbertConfig):
        return COLBERT_SHAPES
    raise TypeError(type(cfg))
