"""Device time of the local layers' banded attention core (ops under the
named scope ``encoder/attention/local``: 14 of ModernBERT's 22 layers)
per thousand docs built in the window."""
from bench import spans
from bench.layer import ms_per_kdoc


def read(x):
    ev = spans.events()
    t = (None if ev is None
         else spans.scope_time_ns(ev, "encoder/attention/local"))
    return ms_per_kdoc(x, t * 1e-9) if t else None
