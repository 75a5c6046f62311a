"""Model FLOP/s of the served step over the chip's bf16 peak: the
FLOPs of one query (encoder, centroid scores, rerank MaxSim) times the
queries completed in the window, over the window."""
from bench.layer import served_query_flops


def read(x):
    if not x.get("served") or x["peak"] is None:
        return None
    rate = x["served"] * served_query_flops(x) / x["window_s"]
    return 100.0 * rate / x["peak"]["bf16_flops_per_s"]
