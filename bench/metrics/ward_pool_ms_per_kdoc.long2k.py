"""Device time of the Ward-pooling kernels (``kernels/ward_pool``; at
N = 2048 the long-doc kernel) per thousand docs built in the window."""
from bench.layer import ms_per_kdoc


def read(x):
    return ms_per_kdoc(x, x["trace"].op_time_s("ward_pool"))
