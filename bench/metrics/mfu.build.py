"""Model FLOP/s of the build over the chip's bf16 peak: per doc the
encoder over its real tokens, Ward's Gram matrix, and the codec's
nearest-centroid scores of its stored vectors; over the window."""
import numpy as np

from bench.layer import encoder_flops


def read(x):
    lens = x.get("doc_lens")
    if lens is None or not len(lens) or x["peak"] is None:
        return None
    w, m, ix = x["work"], x["model"], x["index"]
    dim = int(m["proj_dim"])
    flops = (encoder_flops(m, lens)
             + sum(w.ward_gram_flops(int(n), dim) for n in lens)
             + w.codec_assign_flops(x["stored"], int(ix["n_centroids"]),
                                    dim))
    return 100.0 * flops / x["window_s"] / x["peak"]["bf16_flops_per_s"]
