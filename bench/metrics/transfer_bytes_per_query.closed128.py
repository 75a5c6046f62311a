"""Host<->device bytes per real query served: the ``h2d_bytes`` and
``d2h_bytes`` args of the window's ``repro.`` spans (query vectors out
of the encoder, in again for candidates and rerank; the top-k out) over
the real queries of its microbatches (the ``n`` of
``repro.engine.encode``)."""
from bench import spans


def read(x):
    ev = spans.events()
    if ev is None:
        return None
    total = spans.arg_sum(ev, *spans.BYTES)
    n = sum(spans.arg_values(ev, "n", spans.ENGINE_ENCODE))
    return total / n if total and n else None
