"""The served model from a configuration file: the program's config
objects, and seeded random weights made on the device in one call.

Weights are seeded random in the parameter dtype the configuration
states, laid out as the program's encoder reads them, with the scales of
the configuration's ``init`` group: token embeddings N(0, embed_std^2)
and position embeddings N(0, pos_embed_std^2) (both truncated at two
standard deviations), dense layers N(0, 1/fan_in), the two projections
that write into the residual stream (attention output, second MLP
matrix) scaled by ``residual_out_scale``, LayerNorm scale 1 and bias 0.

The encoder is pre-LayerNorm with no embedding LayerNorm, so at BERT's
0.02 embedding scale the residual branches swamp the token embeddings
and every token's vector comes out nearly the same: queries would then
all rank the corpus alike and no check could tell one answer from
another. Unit-scale token embeddings (the scale BERT's embedding
LayerNorm gives) and GPT-2's residual scaling 1/sqrt(2 * n_layers) keep
tokens apart. The benchmark makes the weights, so the reference may
read them as well.
"""
from __future__ import annotations

import numpy as np


def program_config(cfg: dict):
    """The program's ``ColbertConfig`` for a configuration file."""
    from repro.configs.base import ColbertConfig, TransformerConfig
    m = dict(cfg["model"])
    trunk = TransformerConfig(**m.pop("trunk"))
    return ColbertConfig(trunk=trunk, **m)


def jax_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_params(cfg: dict, seed: int):
    """Seeded encoder weights, made on the device by one jitted call."""
    import jax
    import jax.numpy as jnp
    tr = cfg["model"]["trunk"]
    d, ff, n = int(tr["d_model"]), int(tr["d_ff"]), int(tr["n_layers"])
    V, P = int(tr["vocab_size"]), int(tr["max_seq_len"])
    proj = int(cfg["model"]["proj_dim"])
    dtype = jnp.dtype(tr.get("param_dtype", "float32"))
    scales = cfg["init"]
    out_scale = float(scales["residual_out_scale"])

    def dense(k, shape, scale=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (scale / np.sqrt(shape[-2]))).astype(dtype)

    def norm(*lead):
        return {"scale": jnp.ones(lead + (d,), dtype),
                "bias": jnp.zeros(lead + (d,), dtype)}

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 9)
        emb = lambda k, r, std: (jax.random.truncated_normal(
            k, -2.0, 2.0, (r, d), jnp.float32) * std).astype(dtype)
        attn = {w: {"w": dense(ks[2 + i], (n, d, d),
                               out_scale if w == "wo" else 1.0)}
                for i, w in enumerate(("wq", "wk", "wv", "wo"))}
        return {
            "trunk": {
                "embed": {"table": emb(ks[0], V,
                                       float(scales["embed_std"]))},
                "pos_embed": {"table": emb(ks[1], P,
                                           float(scales["pos_embed_std"]))},
                "dense_layers": {
                    "attn_norm": norm(n), "mlp_norm": norm(n),
                    "attn": attn,
                    "mlp": {"w1": {"w": dense(ks[6], (n, d, ff))},
                            "w2": {"w": dense(ks[7], (n, ff, d),
                                              out_scale)}},
                },
                "final_norm": norm(),
            },
            "proj": {"w": dense(ks[8], (d, proj))},
        }

    return init(jax_key(seed))
