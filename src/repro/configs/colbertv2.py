"""ColBERTv2 — the paper's own model [arXiv:2112.01488].

BERT-base trunk (12L/768/12H, learned positions, post-GELU MLP) + 128-d
linear projection; doc_maxlen=256, query_maxlen=32 (paper Appendix A).
JaColBERTv2 analogue (`jacolbertv2`): same trunk, doc_maxlen=300 — the
"second model / second language" generality axis of paper §4.4.
"""
from repro.configs.base import ColbertConfig, TransformerConfig

TRUNK = TransformerConfig(
    name="colbertv2-trunk",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    d_ff=3072,
    vocab_size=30522,
    pos_emb="learned",
    gated_mlp=False,
    act="gelu",
    norm="layernorm",
    norm_eps=1e-12,
    max_seq_len=512,
    attn_full_threshold=4096,
)

CONFIG = ColbertConfig(
    name="colbertv2",
    trunk=TRUNK,
    proj_dim=128,
    doc_maxlen=256,
    query_maxlen=32,
)

JA_TRUNK = TransformerConfig(
    name="jacolbertv2-trunk",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    d_ff=3072,
    vocab_size=32768,
    pos_emb="learned",
    gated_mlp=False,
    act="gelu",
    norm="layernorm",
    norm_eps=1e-12,
    max_seq_len=512,
    attn_full_threshold=4096,
)

JA_CONFIG = ColbertConfig(
    name="jacolbertv2",
    trunk=JA_TRUNK,
    proj_dim=128,
    doc_maxlen=300,
    query_maxlen=32,
)

SMOKE_TRUNK = TransformerConfig(
    name="colbert-smoke-trunk",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=1024,
    pos_emb="learned",
    gated_mlp=False,
    act="gelu",
    norm="layernorm",
    remat=False,
    max_seq_len=64,
    attn_full_threshold=4096,
)

SMOKE = ColbertConfig(
    name="colbert-smoke",
    trunk=SMOKE_TRUNK,
    proj_dim=32,
    doc_maxlen=48,
    query_maxlen=8,
    n_centroids=32,
)
