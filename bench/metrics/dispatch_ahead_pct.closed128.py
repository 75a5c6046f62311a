"""Share (%) of the window's microbatches that a search lane dispatched
while it still held an unfetched one: the ``ahead`` arg of the window's
``repro.engine.search`` spans (1 ahead, 0 not)."""
from bench import spans


def read(x):
    ev = spans.events()
    ahead = [] if ev is None else spans.arg_values(ev, "ahead", spans.SEARCH)
    return 100.0 * ahead.count(1.0) / len(ahead) if ahead else None
