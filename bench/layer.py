"""Arithmetic shared by the per-layer readers in ``metrics/``.

A reader gets one dict: the reduced trace (``trace``), the chip's peaks
(``peak``), the work functions (``work``), and what its driver recorded
about the window (engine counters, doc lengths, model sizes). Each
returns None where the run gives it nothing to read.
"""
from __future__ import annotations

import numpy as np


def module_ms_per_call(x: dict, *patterns: str):
    """Device milliseconds per execution of the programs named so."""
    tr = x["trace"]
    n = tr.module_count(*patterns)
    return tr.module_time_s(*patterns) * 1e3 / n if n else None


def ms_per_kdoc(x: dict, seconds: float):
    docs = x.get("docs")
    return seconds * 1e3 / docs * 1e3 if docs and seconds > 0 else None


def encoder_flops(model: dict, lens) -> float:
    from bench import work
    t = model["trunk"]
    return work.encoder_flops(int(t["n_layers"]), int(t["d_model"]),
                              int(t["d_ff"]), int(model["proj_dim"]), lens)


def served_query_flops(x: dict) -> float:
    """Model FLOPs of one served query: the encoder over its
    ``query_maxlen`` tokens, the centroid scores, and MaxSim against the
    ``ndocs`` reranked docs at the index's mean stored length."""
    from bench import work
    m, ix = x["model"], x["index"]
    lq, dim = int(m["query_maxlen"]), int(m["proj_dim"])
    return (encoder_flops(m, [lq])
            + work.centroid_score_flops(1, lq, int(ix["n_centroids"]), dim)
            + work.maxsim_flops(lq, dim, int(ix["ndocs"])
                                * x["mean_stored_len"]))


def kernel_roofline(x: dict, pattern: str, flops: float, nbytes: float):
    """Share (%) of the roofline of the kernel ops named ``pattern``."""
    from bench import work
    secs = x["trace"].op_time_s(pattern)
    if secs <= 0 or x["peak"] is None:
        return None
    share, _ = work.roofline_share(flops, nbytes, secs, x["peak"])
    return share


def real_queries(x: dict) -> int:
    return int(np.sum(x["batch_sizes"])) if x.get("batch_sizes") else 0
