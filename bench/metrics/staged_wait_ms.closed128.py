"""Median time an encoded microbatch waits staged before a search lane
takes it: the ``staged_wait_us`` arg of the window's
``repro.engine.search`` spans."""
import numpy as np

from bench import spans


def read(x):
    ev = spans.events()
    waits = [] if ev is None else spans.arg_values(ev, "staged_wait_us",
                                                   spans.SEARCH)
    return float(np.median(waits)) * 1e-3 if waits else None
