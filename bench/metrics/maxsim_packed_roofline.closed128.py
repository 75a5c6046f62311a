"""Share of its roofline that the ``maxsim_packed`` rerank kernel
reaches: MaxSim of the window's real queries against their ``ndocs``
reranked docs at the index's mean stored length, packed codes read
once, over the kernel's device time."""
from bench.layer import kernel_roofline, real_queries


def read(x):
    if not real_queries(x):
        return None
    m, ix, w = x["model"], x["index"], x["work"]
    lq, dim = int(m["query_maxlen"]), int(m["proj_dim"])
    flops, nbytes = 0.0, 0.0
    for n in x["batch_sizes"]:
        f, b = w.packed_rerank_work(
            n, lq, dim, int(ix["quant_bits"]), int(ix["n_centroids"]),
            n * int(ix["ndocs"]) * x["mean_stored_len"])
        flops, nbytes = flops + f, nbytes + b
    return kernel_roofline(x, "maxsim_packed", flops, nbytes)
