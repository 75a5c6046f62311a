"""Model FLOP/s of the long-doc build over the chip's bf16 peak: per doc
the encoder over its valid tokens with banded local layers
(``work_band``), Ward's Gram matrix, and the codec's nearest-centroid
scores of its stored vectors; over the window."""
from bench import work_band


def read(x):
    lens = x.get("doc_lens")
    if lens is None or not len(lens) or x["peak"] is None:
        return None
    w, m, ix = x["work"], x["model"], x["index"]
    dim = int(m["proj_dim"])
    flops = (work_band.encoder_flops(m, lens)
             + sum(w.ward_gram_flops(int(n), dim) for n in lens)
             + w.codec_assign_flops(x["stored"], int(ix["n_centroids"]),
                                    dim))
    return 100.0 * flops / x["window_s"] / x["peak"]["bf16_flops_per_s"]
