"""Generic machinery of one benchmark run: find the cell's files by name,
check the platform, keep the compile cache, time the window, trace it,
run the cell's per-layer readers, and print the result line.

Nothing here knows a cell, configuration, traffic or metric by name:

* ``BENCHMARK.json`` names a workload's configuration and traffic;
* ``bench/configs/<config>.json`` holds the configuration as it runs;
* ``bench/traffic/<traffic>.json`` holds the traffic's parameters and
  names its driver, ``bench/drivers/<driver>.py``;
* ``bench/metrics/<metric>.py`` reads one per-layer metric.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
SCRATCH_DIR = os.path.join(BENCH_DIR, ".scratch")
WINDOW_SPAN = "bench.window"    # host span around the measured window


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def load_module(path: str, name: Optional[str] = None):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = name or "bench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Cell:
    """One workload with its files read in."""
    root: str
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)


def load_cell(root: str, workload: str) -> Cell:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    bdir = os.path.join(root, bench["paths"][0])
    cfg = read_json(os.path.join(bdir, "configs", wl["config"] + ".json"))
    traffic = read_json(os.path.join(bdir, "traffic",
                                     wl["traffic"] + ".json"))
    return Cell(root, bench, wl, cfg, traffic)


def devices(chips: int, require_tpu: bool = True):
    """The cell's devices; raises ``NoChip`` unless JAX has at least
    ``chips`` accelerators (a CPU never counts)."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout;
    every program is kept, however fast it compiled."""
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Counts XLA compilations — programs built, not hits of the
    persistent cache — and the seconds spent building or loading."""

    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    @property
    def n(self) -> int:
        return self.requests - self.hits

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _hit(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def bytes_in_use(dev) -> int:
    gc.collect()
    return int((dev.memory_stats() or {}).get("bytes_in_use", 0))


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


@dataclass
class Check:
    """One number compared for ``correct``: it must stay at or below its
    limit (a missing or nan number never does)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (self.value is not None and not math.isnan(self.value)
                and self.value <= self.limit)


@dataclass
class Run:
    """What a driver hands back to the harness."""
    attempted: int = 0
    failed: int = 0
    metrics: Dict[str, float] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    layer: Dict[str, Any] = field(default_factory=dict)   # reader inputs
    memory_peak_bytes: int = 0
    trace_dir: Optional[str] = None


@dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and hooks."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float
    compiles: Any
    scratch: str
    log: Callable[[str], None]
    options: Dict[str, Any] = field(default_factory=dict)

    def setup_s(self) -> float:
        return time.perf_counter() - self.t_start

    def mark(self, what: str) -> None:
        """Log the clock (``time.perf_counter``, the host's monotonic
        clock) as a phase of the run ends."""
        self.log(f"at {time.perf_counter():.3f}: {what} "
                 f"(process start {self.t_start:.3f})")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def start_trace(path: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # spans come from TraceAnnotation
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


def per_layer(cell: Cell, run: Run, dev) -> tuple:
    """Reduce the traced window and run the cell's readers: (metrics,
    device dict additions, breakdown)."""
    from bench import trace as tr
    from bench import work
    events = tr.load(run.trace_dir)
    spans = [e for e in events if e.name == WINDOW_SPAN
             and not tr.is_device(e.plane)]
    window = ((spans[0].start_ns, spans[0].end_ns) if spans else None)
    summary = tr.reduce(events, window, host_prefix="bench.",
                        exclude=(WINDOW_SPAN,))
    # peaks are a chip's; a CPU rehearsal has none and its readers of
    # shares of a peak read nothing
    peak = work.peaks(dev.device_kind) if dev.platform != "cpu" else None
    inputs = dict(run.layer, trace=summary, cell=cell, peak=peak, work=work)
    out = {}
    bdir = cell.path(cell.bench["paths"][0], "metrics")
    for m in cell.per_layer():
        reader = load_module(os.path.join(bdir, m["name"] + ".py"))
        v = reader.read(inputs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"busy_s": summary.busy_s, "window_s": summary.window_s}
    for kind, table in (("program", summary.by_module),
                        ("op", summary.by_op)):
        top = sorted(table.items(), key=lambda kv: -kv[1])[:25]
        log(f"device time by {kind}: " + ", ".join(
            f"{k} {v * 1e-9:.4f}s" for k, v in top))
    return out, device, summary.breakdown()


def result_line(cell: Cell, run: Run, devs, metrics: dict,
                extra_device: dict, breakdown: Optional[dict]) -> dict:
    correct = (run.failed == 0 and bool(run.checks)
               and all(c.ok for c in run.checks))
    line = {
        "correct": correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": dict({"platform": devs[0].platform,
                        "kind": devs[0].device_kind,
                        "count": len(devs),
                        "memory_peak_bytes": int(run.memory_peak_bytes)},
                       **extra_device),
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {c.name: {"value": _num(c.value), "limit": c.limit,
                                 "ok": c.ok} for c in run.checks}
    return line


def _num(v):
    """JSON has no inf or nan: those print as null (and never pass)."""
    return float(v) if v is not None and math.isfinite(v) else None


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             options: Optional[dict] = None, t_start: float = None
             ) -> dict:
    """One run of one cell; returns the result line (a dict)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    if require_tpu:
        enable_compile_cache()
    devs = devices(int(cell.workload["chips"]), require_tpu)
    bdir = cell.path(cell.bench["paths"][0])
    driver = load_module(os.path.join(bdir, "drivers",
                                      cell.traffic["driver"] + ".py"))
    scratch = os.path.join(SCRATCH_DIR, f"run{os.getpid()}")
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), devices=devs, t_start=t_start,
                  compiles=Compiles(), scratch=scratch, log=log,
                  options=dict(options or {}))
    if trace:
        ctx.options.setdefault("trace_dir", os.path.join(scratch, "trace"))
    ctx.mark("devices found")
    try:
        run = driver.run(ctx)
        ctx.mark("run checked")
        if trace:
            metrics, extra, bd = per_layer(cell, run, devs[0])
        else:
            names = {m["name"] for m in cell.end_to_end()}
            metrics = {k: {"value": float(v), "unit": _unit(cell, k)}
                       for k, v in run.metrics.items() if k in names}
            extra, bd = {}, None
    finally:
        import shutil
        shutil.rmtree(scratch, ignore_errors=True)
        ctx.mark("scratch removed")
    line = result_line(cell, run, devs, metrics, extra, bd)
    for c in run.checks:
        log(f"compared {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    return line


def _unit(cell: Cell, name: str) -> str:
    return [m["unit"] for m in cell.bench["end_to_end"]
            if m["name"] == name][0]
