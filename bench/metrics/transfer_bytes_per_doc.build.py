"""Host<->device bytes per doc built: the ``h2d_bytes`` and
``d2h_bytes`` args of the window's ``repro.`` spans (tokens in, pooled
rows out, pooled vectors in again for the codec, codes out) over the
docs in the window's shards."""
from bench import spans


def read(x):
    ev = spans.events()
    total = None if ev is None else spans.arg_sum(ev, *spans.BYTES)
    return total / x["docs"] if total and x.get("docs") else None
