"""Device time of the doc-encode program (``models/colbert.py``
``encode_docs``) per thousand long docs built in the window."""
from bench.layer import ms_per_kdoc


def read(x):
    return ms_per_kdoc(x, x["trace"].module_time_s("encode_docs"))
