"""GTE-ModernColBERT-v1 (lightonai/GTE-ModernColBERT-v1): a ColBERT head
on ModernBERT-base (arXiv:2412.13663; the shape of
Alibaba-NLP/gte-modernbert-base).

ModernBERT-base: 22 layers, d_model 768, 12 heads of 64, GeGLU MLP with
``Wi`` 768 -> 2 x 1152 (exact-erf GELU on the first half, times the
second), bias-free LayerNorm (eps 1e-5) and linears, a LayerNorm on the
token embeddings, no position embedding, and no pre-attention norm on
layer 0. Layer i is global when ``i % 3 == 0`` (RoPE theta 160000);
the others are local, attending |i - j| <= 64 (``local_attention`` 128,
RoPE theta 10000). The ColBERT head: a bias-free 768 -> 128 projection,
query_maxlen 32. Docs run at 2048 tokens (long-document retrieval; the
model holds 8192 positions).

Marker ids: ModernBERT's [CLS] 50281 and [MASK] 50284; [Q] and [D] take
the first two unused ids, 50285 and 50286 (the system's [CLS][Q]/[D]
marker convention and [MASK] query expansion).
"""
from repro.configs.base import ColbertConfig, TransformerConfig

TRUNK = TransformerConfig(
    name="gte-moderncolbert-trunk",
    n_layers=22,
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    d_ff=1152,
    vocab_size=50368,
    pos_emb="rope",
    rope_theta=160_000.0,
    local_rope_theta=10_000.0,
    local_window=64,
    global_every=3,
    gated_mlp=True,
    act="gelu_erf",
    norm="layernorm",
    norm_eps=1e-5,
    norm_bias=False,
    embed_norm=True,
    first_attn_norm=False,
    max_seq_len=8192,
    attn_full_threshold=512,
)

CONFIG = ColbertConfig(
    name="gte-moderncolbert",
    trunk=TRUNK,
    proj_dim=128,
    doc_maxlen=2048,
    query_maxlen=32,
    cls_id=50281,
    mask_id=50284,
    q_marker_id=50285,
    d_marker_id=50286,
)

# CPU-test size: global, local, local and a trailing global layer
SMOKE_TRUNK = TransformerConfig(
    name="gte-moderncolbert-smoke-trunk",
    n_layers=4,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=96,
    vocab_size=1024,
    pos_emb="rope",
    rope_theta=160_000.0,
    local_rope_theta=10_000.0,
    local_window=8,
    global_every=3,
    gated_mlp=True,
    act="gelu_erf",
    norm="layernorm",
    norm_eps=1e-5,
    norm_bias=False,
    embed_norm=True,
    first_attn_norm=False,
    remat=False,
    max_seq_len=64,
    attn_full_threshold=16,
)

SMOKE = ColbertConfig(
    name="gte-moderncolbert-smoke",
    trunk=SMOKE_TRUNK,
    proj_dim=32,
    doc_maxlen=40,
    query_maxlen=8,
    n_centroids=16,
    cls_id=1000,
    mask_id=1001,
    q_marker_id=1002,
    d_marker_id=1003,
)
