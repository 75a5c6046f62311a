"""Device time of the doc encoder's attention (ops under the named scope
``encoder/attention``, one per transformer layer) per thousand docs
built in the window."""
from bench import spans
from bench.layer import ms_per_kdoc


def read(x):
    ev = spans.events()
    t = None if ev is None else spans.scope_time_ns(ev, "encoder/attention")
    return ms_per_kdoc(x, t * 1e-9) if t else None
