"""Device idle time while the build thread copies a batch's tokens over
and dispatches its encode and pooling (``repro.indexer.encode``,
``repro.indexer.pool``), as a share of the traced window."""
from bench import spans


def read(x):
    return spans.idle_pct(*spans.DISPATCH)
