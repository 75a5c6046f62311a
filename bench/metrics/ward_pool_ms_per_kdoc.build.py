"""Device time of the Ward-pooling kernel (``kernels/ward_pool``) per
thousand docs built in the window."""
from bench.layer import ms_per_kdoc


def read(x):
    return ms_per_kdoc(x, x["trace"].op_time_s("ward_pool"))
