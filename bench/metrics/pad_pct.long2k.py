"""Share (%) of the encoder's token slots that hold padding: one minus
the ``valid_tokens`` over the ``tokens`` args of the window's
``repro.indexer.encode`` spans."""
from bench import spans

ENCODE = "repro.indexer.encode"


def read(x):
    ev = spans.events()
    if ev is None:
        return None
    slots = sum(spans.arg_values(ev, "tokens", ENCODE))
    valid = sum(spans.arg_values(ev, "valid_tokens", ENCODE))
    return 100.0 * (1.0 - valid / slots) if slots else None
