"""Share of its roofline that the ``plaid_probe`` kernel reaches: the
centroid interaction of the window's real queries, each against every
stored vector (at nprobe 2 over 32 query tokens nearly every doc is a
candidate, so this is an upper bound of the work), over the kernel's
device time."""
from bench.layer import kernel_roofline, real_queries


def read(x):
    nq = real_queries(x)
    if not nq:
        return None
    m, ix, w = x["model"], x["index"], x["work"]
    lq, dim = int(m["query_maxlen"]), int(m["proj_dim"])
    flops, nbytes = 0.0, 0.0
    for n in x["batch_sizes"]:
        f, b = w.probe_work(n, lq, dim, int(ix["n_centroids"]),
                            n * x["stored_vectors"], x["stored_vectors"])
        flops, nbytes = flops + f, nbytes + b
    return kernel_roofline(x, "plaid_probe", flops, nbytes)
