"""Mean real queries per engine batch over ``max_batch``
(``EngineStats.batch_sizes``)."""
import numpy as np


def read(x):
    b = x.get("batch_sizes")
    return float(100.0 * np.mean(b) / x["max_batch"]) if b else None
