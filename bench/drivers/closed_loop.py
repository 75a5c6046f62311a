"""Closed loop: a fixed number of clients, each sending one
single-query request and waiting for its reply before the next.

``qps`` counts the requests completed inside the window over the
window's length; requests still in flight when it closes are waited
for (and checked) but not counted.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import harness, serving


def run(ctx):
    served = serving.Served(ctx)
    tr = ctx.cell.traffic
    n_clients = int(tr["clients"])
    out = harness.Run()
    out.metrics["setup_s"] = ctx.setup_s()
    out.metrics["hbm_bytes_per_doc"] = served.hbm_bytes_per_doc
    engine = served.engine
    n_q = len(served.q_tokens)
    logs = [[] for _ in range(n_clients)]       # (row, future) per client
    stop = threading.Event()

    def client(c: int) -> None:
        rng = np.random.default_rng([ctx.seed, 7, c])
        while not stop.is_set():
            r = int(rng.integers(n_q))
            f = engine.submit(served.q_tokens[r][None])
            logs[c].append((r, f))
            f._event.wait(120.0)
            if f._error is not None or not f.done():
                return

    if ctx.trace:
        served.annotate()
        harness.start_trace(ctx.options["trace_dir"])
    import jax
    s0 = served.engine.stats.snapshot()
    c0 = ctx.compiles.n
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(max(t0 + ctx.seconds - time.perf_counter(), 0.0))
        stop.set()
        t_end = t0 + ctx.seconds
    s1 = served.engine.stats.snapshot()
    n_win = s1["batches"] - s0["batches"]
    layer = served.layer_inputs(n_win, s1["served"] - s0["served"],
                                ctx.seconds)
    for t in threads:
        t.join(150.0)
    if ctx.trace:
        harness.stop_trace()
        out.trace_dir = ctx.options["trace_dir"]
    nc = ctx.compiles.n - c0
    futs = [(r, f) for lg in logs for r, f in lg]
    ok = [(r, f) for r, f in futs if f.done() and f._error is None]
    in_window = sum(1 for _, f in ok if f.done_t <= t_end)
    out.attempted = len(futs)
    out.failed = len(futs) - len(ok)
    out.metrics["qps"] = in_window / ctx.seconds
    out.layer = dict(layer, served=in_window)
    ctx.log(f"window: {n_clients} clients, {in_window} requests in "
            f"{ctx.seconds} s ({out.metrics['qps']:.1f} QPS), "
            f"{out.failed} failed, {n_win} batches (mean "
            f"{(s1['served'] - s0['served']) / max(n_win, 1):.2f}), "
            f"compiles in window {nc}")
    done = serving.results(ok)
    out.memory_peak_bytes = served.close()
    out.checks = serving.check(served, done)
    return out
