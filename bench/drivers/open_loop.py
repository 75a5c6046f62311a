"""Open loop: independent single-query requests that arrive on their own
seeded Poisson schedule at a fixed rate, whatever the system's replies.
The schedule is a Poisson process conditioned on its count: ``rate_qps``
x the window's seconds arrival times, drawn uniformly over the window
and sorted, so every seed offers the same number of requests and ``qps``
does not spread with a seed's Poisson count.

One submitter thread sends each request at its arrival time (in a burst
when it has fallen behind) and never waits for a reply. The objects
set-up made are frozen out of the collector's reach (``gc.freeze``), so
a collection does not hold the submitter up. ``qps`` counts the requests
completed inside the window over its length; requests still in flight
when it closes are waited for (and checked) but not counted. Latency
percentiles and how late the submitter ran are logged, not compared.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from bench import harness, serving


def run(ctx):
    served = serving.Served(ctx)
    tr = ctx.cell.traffic
    rate = float(tr["rate_qps"])
    out = harness.Run()
    out.metrics["setup_s"] = ctx.setup_s()
    out.metrics["hbm_bytes_per_doc"] = served.hbm_bytes_per_doc
    engine = served.engine
    n_q = len(served.q_tokens)
    rng = np.random.default_rng([ctx.seed, 7])
    n = int(round(rate * ctx.seconds))
    arrivals = np.sort(rng.uniform(0.0, ctx.seconds, n))
    rows = rng.integers(n_q, size=n)
    sent = []                     # (row, future, send time)
    lags = []

    def submitter(t0: float) -> None:
        for a, r in zip(arrivals, rows):
            wait = t0 + a - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            now = time.perf_counter()
            lags.append(now - t0 - a)
            sent.append((int(r), engine.submit(served.q_tokens[r][None]),
                         now))

    if ctx.trace:
        served.annotate()
        harness.start_trace(ctx.options["trace_dir"])
    import jax
    gc.collect()
    gc.freeze()
    s0 = engine.stats.snapshot()
    c0 = ctx.compiles.n
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        t0 = time.perf_counter()
        th = threading.Thread(target=submitter, args=(t0,), daemon=True)
        th.start()
        time.sleep(max(t0 + ctx.seconds - time.perf_counter(), 0.0))
        t_end = t0 + ctx.seconds
    th.join(60.0)
    s1 = engine.stats.snapshot()
    n_win = s1["batches"] - s0["batches"]
    layer = served.layer_inputs(n_win, s1["served"] - s0["served"],
                                ctx.seconds)
    serving.wait_all([f for _, f, _ in sent], 120.0)
    if ctx.trace:
        harness.stop_trace()
        out.trace_dir = ctx.options["trace_dir"]
    gc.unfreeze()
    nc = ctx.compiles.n - c0
    ok = [(r, f, t) for r, f, t in sent if f.done() and f._error is None]
    in_window = [f.done_t - t for _, f, t in ok if f.done_t <= t_end]
    out.attempted = len(sent)
    out.failed = len(sent) - len(ok)
    out.metrics["qps"] = len(in_window) / ctx.seconds
    out.layer = dict(layer, served=len(in_window))
    lat = np.asarray(in_window) * 1e3 if in_window else np.zeros(1)
    ctx.log(f"window: offered {rate:.1f} QPS, sent {len(sent)}, "
            f"{len(in_window)} completed in {ctx.seconds} s "
            f"({out.metrics['qps']:.1f} QPS), {out.failed} failed, "
            f"{n_win} batches (mean {(s1['served'] - s0['served']) / max(n_win, 1):.2f}), "
            f"latency ms p50 {np.percentile(lat, 50):.2f} p95 "
            f"{np.percentile(lat, 95):.2f} p99 {np.percentile(lat, 99):.2f}, "
            f"submitter lag ms p50 {np.median(lags) * 1e3:.3f} max "
            f"{max(lags, default=0.0) * 1e3:.3f}, compiles in window {nc}")
    done = serving.results([(r, f) for r, f, _ in ok])
    out.memory_peak_bytes = served.close()
    out.checks = serving.check(served, done)
    return out
