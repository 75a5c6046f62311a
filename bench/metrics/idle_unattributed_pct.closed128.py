"""Device idle time under no ``repro.`` span on any thread, as a share
of the traced window: what the serving spans leave unexplained."""
from bench import spans


def read(x):
    return spans.unattributed_pct()
