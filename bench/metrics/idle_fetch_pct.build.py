"""Device idle time while the build thread fetches a batch's pooled rows
and counts (``repro.indexer.fetch``: the compaction's device->host copy
and the raw-count sync), as a share of the traced window."""
from bench import spans


def read(x):
    return spans.idle_pct(spans.FETCH)
