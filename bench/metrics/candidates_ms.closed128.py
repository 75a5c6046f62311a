"""Device time per execution of the PLAID candidate program, stages 1-3
(``core/plaid.py`` ``_device_candidates``)."""
from bench.layer import module_ms_per_call


def read(x):
    return module_ms_per_call(x, "_device_candidates")
