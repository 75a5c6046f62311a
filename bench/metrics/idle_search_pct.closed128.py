"""Device idle time while a search lane holds a microbatch
(``repro.engine.search``: candidates, rerank and top-k), as a share of
the traced window."""
from bench import spans


def read(x):
    return spans.idle_pct(spans.SEARCH)
