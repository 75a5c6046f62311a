"""Reduce a profiler trace to the numbers the benchmark reports.

A trace is read into plain ``Event`` tuples (``load``), so everything
below works on a synthetic event list as well as on a real
``.xplane.pb``. Nothing here imports a TPU library at module load.

* device planes are the planes named ``/device:...``; within one, the
  op events (line ``XLA Ops`` where there is one) are the work;
* busy time is the union of a device's op intervals, clipped to the
  traced window, averaged over the devices;
* time is attributed to a program (the ``XLA Modules`` line) and to an
  op by name, so a reader can ask for
  the device time of the query-encode program or of a Pallas kernel;
* idle gaps on a device are labelled with the host span (the
  benchmark's ``TraceAnnotation``s) that overlaps them most.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged: List[List[float]], lo: float, hi: float) -> float:
    """Length of ``merged`` inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def op_name(text: str) -> str:
    """An op event may carry its whole HLO instruction
    (``%plaid_probe_pallas.1 = f32[...] custom-call(...)``); its name is
    what stands before `` = `` — operands name other ops."""
    head, eq, _ = text.partition(" = ")
    return head.lstrip("%") if eq else text


def load(path: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``path`` (a file or a
    trace directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, op_name(ev.name),
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


@dataclass
class Summary:
    window_ns: Tuple[float, float]
    n_devices: int
    busy_ns: float                      # mean over devices
    by_op: Dict[str, float] = field(default_factory=dict)      # device 0
    by_module: Dict[str, float] = field(default_factory=dict)  # device 0
    module_calls: Dict[str, int] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    @property
    def idle_share(self) -> float:
        w = self.window_ns[1] - self.window_ns[0]
        return 1.0 - self.busy_ns / w if w > 0 else 0.0

    def op_time_s(self, *patterns: str) -> float:
        """Device-0 seconds of ops whose name holds any pattern."""
        return sum(t for n, t in self.by_op.items()
                   if any(p in n for p in patterns)) * 1e-9

    def module_time_s(self, *patterns: str) -> float:
        return sum(t for n, t in self.by_module.items()
                   if any(p in n for p in patterns)) * 1e-9

    def module_count(self, *patterns: str) -> int:
        return sum(c for n, c in self.module_calls.items()
                   if any(p in n for p in patterns))

    def breakdown(self, n: int = 10) -> dict:
        """Top device ops by time, and idle time summed by the host span
        that overlapped it (seconds)."""
        ops = sorted(self.by_op.items(), key=lambda kv: -kv[1])[:n]
        idle: Dict[str, float] = {}
        for k, v in self.gaps:
            idle[k] = idle.get(k, 0.0) + v
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def _strip(name: str) -> str:
    """Module names carry a run id in parentheses: ``jit_f(123)``."""
    i = name.find("(")
    return name[:i] if i > 0 else name


def reduce(events: List[Event], window_ns: Optional[Tuple[float, float]]
           = None, host_prefix: str = "", exclude: Tuple[str, ...] = ()
           ) -> Summary:
    """Busy share, per-op and per-program device time, and labelled idle
    gaps of the events inside ``window_ns`` (default: their extent).
    ``host_prefix`` picks which host spans may label a gap, less the
    names in ``exclude`` (such as the span of the window itself)."""
    dev = [e for e in events if is_device(e.plane)]
    planes = sorted({e.plane for e in dev})
    if window_ns is None:
        ev = dev or events
        window_ns = (min(e.start_ns for e in ev), max(e.end_ns for e in ev))
    lo, hi = window_ns
    busy, first = [], None
    for p in planes:
        mine = [e for e in dev if e.plane == p]
        ops = [e for e in mine if e.line == OPS_LINE] or [
            e for e in mine if e.line != MODULES_LINE]
        merged = union((e.start_ns, e.end_ns) for e in ops)
        busy.append(covered(merged, lo, hi))
        if first is None:
            first = (mine, ops, merged)
    s = Summary(window_ns, len(planes),
                sum(busy) / len(busy) if busy else 0.0)
    if first is None:
        return s
    mine, ops, merged = first
    for e in ops:
        t = max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
        if t <= 0:
            continue
        s.by_op[e.name] = s.by_op.get(e.name, 0.0) + t
    for e in (e for e in mine if e.line == MODULES_LINE):
        t = max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
        if t <= 0:
            continue
        k = _strip(e.name)
        s.by_module[k] = s.by_module.get(k, 0.0) + t
        s.module_calls[k] = s.module_calls.get(k, 0) + 1
    s.gaps = label_gaps(merged, lo, hi, [
        e for e in events if not is_device(e.plane)
        and e.name.startswith(host_prefix) and e.name not in exclude
        and e.dur_ns > 0])
    return s


def label_gaps(merged: List[List[float]], lo: float, hi: float,
               host: List[Event]) -> List[Tuple[str, float]]:
    """Idle gaps between busy intervals inside [lo, hi), longest first,
    each labelled ``<host span>`` by its largest overlap (``idle`` where
    no host span overlaps)."""
    import bisect
    host = sorted(host, key=lambda h: h.start_ns)
    starts = [h.start_ns for h in host]
    longest = max((h.dur_ns for h in host), default=0.0)
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        best, label = 0.0, "idle"
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        for h in host[i:j]:
            ov = min(h.end_ns, b) - max(h.start_ns, a)
            if ov > best:
                best, label = ov, h.name
        gaps.append((label, b - a))
    gaps.sort(key=lambda g: -g[1])
    return gaps
