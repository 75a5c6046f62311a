"""Seeded weights for a ModernBERT-trunk configuration (GTE-ModernColBERT),
made on the device in one jitted call, laid out as the program's encoder
reads them (``bench/model.py`` makes the BERT-trunk ones).

Scales come from the configuration's ``init`` group: token embeddings
N(0, embed_std^2) truncated at two standard deviations (a bias-free
LayerNorm follows them), dense layers N(0, 1/fan_in), the two
projections that write into the residual stream (attention output,
GeGLU's ``W2``) scaled by ``residual_out_scale``, LayerNorm scales 1 (no
biases). The GeGLU input matrix ``Wi`` is held as its two halves, ``w1``
(the input) and ``w3`` (the gate). No position embedding: RoPE.
"""
from __future__ import annotations

import numpy as np

from bench.model import jax_key


def make_params(cfg: dict, seed: int):
    """Seeded encoder weights, made on the device by one jitted call."""
    import jax
    import jax.numpy as jnp
    tr = cfg["model"]["trunk"]
    d, ff, n = int(tr["d_model"]), int(tr["d_ff"]), int(tr["n_layers"])
    V = int(tr["vocab_size"])
    proj = int(cfg["model"]["proj_dim"])
    dtype = jnp.dtype(tr.get("param_dtype", "float32"))
    scales = cfg["init"]
    out_scale = float(scales["residual_out_scale"])

    def dense(k, shape, scale=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (scale / np.sqrt(shape[-2]))).astype(dtype)

    def norm(*lead):
        return {"scale": jnp.ones(lead + (d,), dtype)}

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 9)
        attn = {w: {"w": dense(ks[1 + i], (n, d, d),
                               out_scale if w == "wo" else 1.0)}
                for i, w in enumerate(("wq", "wk", "wv", "wo"))}
        return {
            "trunk": {
                "embed": {"table": (jax.random.truncated_normal(
                    ks[0], -2.0, 2.0, (V, d), jnp.float32)
                    * float(scales["embed_std"])).astype(dtype)},
                "embed_norm": norm(),
                "dense_layers": {
                    "attn_norm": norm(n), "mlp_norm": norm(n),
                    "attn": attn,
                    "mlp": {"w1": {"w": dense(ks[5], (n, d, ff))},
                            "w3": {"w": dense(ks[6], (n, d, ff))},
                            "w2": {"w": dense(ks[7], (n, ff, d),
                                              out_scale)}},
                },
                "final_norm": norm(),
            },
            "proj": {"w": dense(ks[8], (d, proj))},
        }

    return init(jax_key(seed))
