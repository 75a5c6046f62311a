"""The program's own host spans (``repro.*``) in a traced run: the
device's idle time under them, their args, and the device time of ops
under a named scope.

The per-layer readers get the reduced trace (``bench/trace.py``), not
its events, so this module reads the run's ``.xplane.pb`` a second
time, once per process, from where ``harness.run_cell`` has the trace
written (``SCRATCH_DIR/run<pid>/trace``). It keeps only the device op
events of the first device (with the scope path of each op where the
trace carries one), the ``repro.`` host spans with their args, and the
``bench.window`` span. A run of a program without such spans reads
nothing here, and each reader returns None.

Every reduction works on an ``Events`` made of plain lists, so the
tests build one by hand. Idle time is the window less the union of the
device's op intervals; "under" a set of spans means inside the union
of those spans, on any thread.
"""
from __future__ import annotations

import glob
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness
from bench.trace import MODULES_LINE, OPS_LINE, is_device, union

PREFIX = "repro."
# span names (``src/repro/obs.py``); literal here, so the benchmark runs
# against a program that has no such module
FETCH = "repro.indexer.fetch"
DISPATCH = ("repro.indexer.encode", "repro.indexer.pool")
SEARCH = "repro.engine.search"
ENGINE_ENCODE = "repro.engine.encode"
BYTES = ("h2d_bytes", "d2h_bytes")

@dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: Dict[str, object] = field(default_factory=dict)


@dataclass
class Events:
    """One traced window: device-0 busy intervals, the op intervals
    that carry a scope path, and the program's spans."""
    window: Tuple[float, float]
    busy: List[List[float]]                       # merged, clipped
    spans: List[Span]                             # repro.*, in window
    scoped: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, *names: str) -> List[Span]:
        return [s for s in self.spans if s.name in names]


def _overlap(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clipped(ev: Events, spans: Sequence[Span]) -> List[List[float]]:
    lo, hi = ev.window
    return union((max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans)


def idle_under_ns(ev: Events, *names: str) -> float:
    """Device idle time inside the union of the spans named so."""
    cover = _clipped(ev, ev.named(*names))
    return sum(e - s for s, e in cover) - _overlap(cover, ev.busy)


def unattributed_idle_ns(ev: Events) -> float:
    """Device idle time that no ``repro.`` span on any thread covers."""
    cover = _clipped(ev, ev.spans)
    busy = sum(e - s for s, e in ev.busy)
    covered = sum(e - s for s, e in cover)
    return ev.window_ns - busy - covered + _overlap(cover, ev.busy)


def arg_values(ev: Events, key: str, *names: str) -> List[float]:
    """The arg ``key`` of every span (of the given names) that has it."""
    pick = ev.named(*names) if names else ev.spans
    return [float(s.args[key]) for s in pick if key in s.args]


def arg_sum(ev: Events, *keys: str) -> Optional[float]:
    """Sum of the args ``keys`` over the window's spans (None where no
    span carries any of them)."""
    vals = [v for k in keys for v in arg_values(ev, k)]
    return float(sum(vals)) if vals else None


def in_scope(path: str, scope: str) -> bool:
    """Whether an op's scope path lies under ``scope`` (``a/b``): its
    components hold ``a`` and, later, ``b`` (transforms such as a scan's
    ``while/body`` may sit between them)."""
    parts = path.split("/")
    i = 0
    for want in scope.split("/"):
        while i < len(parts) and parts[i] != want:
            i += 1
        if i == len(parts):
            return False
        i += 1
    return True


def scope_time_ns(ev: Events, scope: str) -> Optional[float]:
    """Device-0 time of the ops under ``scope`` (union of their
    intervals, inside the window); None where no op carries a scope."""
    if not ev.scoped:
        return None
    return sum(e - s for s, e in union(
        (s, e) for p, s, e in ev.scoped if in_scope(p, scope)))


# ---------------------------------------------------------------- loading
_CACHE: Dict[str, Optional[Events]] = {}


def trace_dir() -> str:
    return os.path.join(harness.SCRATCH_DIR, f"run{os.getpid()}", "trace")


def events() -> Optional[Events]:
    """This process's traced window, read once per profile file; None
    when there is no trace, no window, or no ``repro.`` span in it."""
    files = sorted(glob.glob(os.path.join(trace_dir(), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    path = files[-1]
    key = f"{path}:{os.stat(path).st_mtime_ns}"
    if key not in _CACHE:
        _CACHE[key] = load(path)
        if _CACHE[key] is not None:
            log_split(_CACHE[key])
    return _CACHE[key]


def load(path: str) -> Optional[Events]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window = None
    spans = []
    dev = {}
    for plane in data.planes:
        if is_device(plane.name):
            ops = _ops(plane)
            if ops:     # as ``trace.reduce``: planes with events only
                dev[plane.name] = ops
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == harness.WINDOW_SPAN:
                    window = (float(e.start_ns), float(e.end_ns))
                elif name.startswith(PREFIX):
                    spans.append(Span(name, float(e.start_ns),
                                      float(e.end_ns), dict(e.stats)))
    if window is None:
        return None
    lo, hi = window
    spans = [s for s in spans if lo <= s.start_ns < hi]
    if not spans:
        return None
    busy, scoped = [], []
    if dev:
        scopes = op_scopes(path)
        for name, s, t in dev[sorted(dev)[0]]:
            s, t = max(s, lo), min(t, hi)
            if t > s:
                busy.append((s, t))
                if name in scopes:
                    scoped.append((scopes[name], s, t))
    return Events(window, union(busy), spans, scoped)


def _ops(plane) -> List[Tuple[str, float, float]]:
    lines = [ln for ln in plane.lines if ln.name == OPS_LINE] or [
        ln for ln in plane.lines if ln.name != MODULES_LINE]
    return [(e.name, float(e.start_ns), float(e.end_ns))
            for line in lines for e in line.events]


# A device op's scope path is the stat ``tf_op`` of its event metadata,
# which ``ProfileData`` does not expose; ``op_scopes`` reads it from the
# ``.xplane.pb`` wire format (``XSpace.planes`` = 1; ``XPlane`` name = 2,
# event_metadata = 4, stat_metadata = 5; ``XEventMetadata`` name = 2,
# display_name = 4, stats = 5; ``XStat`` metadata_id = 1, str_value = 5,
# ref_value = 7; ``XStatMetadata`` name = 2; map entries key 1, value 2).
SCOPE_STAT = "tf_op"


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of one message in ``b[lo:hi]``: an int, or
    a (start, end) range for a length-delimited field."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, v


def _text(b: bytes, r) -> str:
    return b[r[0]:r[1]].decode("utf-8", "replace")


def op_scopes(path: str) -> Dict[str, str]:
    """{device op event name: its scope path}; empty where the trace
    carries none (or cannot be read so)."""
    try:
        with open(path, "rb") as fh:
            b = fh.read()
        out: Dict[str, str] = {}
        for f, plane in _fields(b):
            if f == 1:
                _plane_scopes(b, plane, out)
        return out
    except (ValueError, IndexError, TypeError) as e:
        print(f"op scopes not read: {e!r}", file=sys.stderr, flush=True)
        return {}


def _plane_scopes(b: bytes, r, out: Dict[str, str]) -> None:
    name, events, stat_names = "", [], {}
    for f, v in _fields(b, *r):
        if f == 2:
            name = _text(b, v)
            if not is_device(name):
                return
        elif f in (4, 5):
            entry = dict(_fields(b, *v))
            if 2 not in entry:
                continue
            if f == 4:
                events.append(entry[2])
            else:
                meta = dict(_fields(b, *entry[2]))
                stat_names[entry.get(1, 0)] = _text(b, meta[2]) \
                    if 2 in meta else ""
    for r_ev in events:
        names, scope = [], None
        for f, v in _fields(b, *r_ev):
            if f in (2, 4):
                names.append(_text(b, v))
            elif f == 5:
                stat = dict(_fields(b, *v))
                if stat_names.get(stat.get(1)) != SCOPE_STAT:
                    continue
                if 5 in stat:
                    scope = _text(b, stat[5])
                elif 7 in stat:
                    scope = stat_names.get(stat[7])
        if scope:
            for n in names:
                out[n] = scope


def log_split(ev: Events) -> None:
    """One stderr line per traced run: device idle seconds under each
    ``repro.`` span name (with the args its spans carry), and under
    none."""
    args: Dict[str, set] = {}
    for s in ev.spans:
        args.setdefault(s.name, set()).update(s.args)
    idle = {n: idle_under_ns(ev, n) * 1e-9 for n in args}
    parts = [f"{n}[{','.join(sorted(args[n]))}] {v:.4f}s"
             for n, v in sorted(idle.items(), key=lambda kv: -kv[1])]
    print(f"idle by repro span (window {ev.window_ns * 1e-9:.3f} s; "
          f"spans overlap, so the parts may sum past the whole): "
          + ", ".join(parts)
          + f"; under no repro span {unattributed_idle_ns(ev) * 1e-9:.4f}s; "
          f"scoped device ops {len(ev.scoped)}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------- readers
def idle_pct(*names: str) -> Optional[float]:
    """Device idle under the spans named so, % of the window (None where
    the window holds none of them)."""
    ev = events()
    if ev is None or not ev.named(*names):
        return None
    return 100.0 * idle_under_ns(ev, *names) / ev.window_ns


def unattributed_pct() -> Optional[float]:
    ev = events()
    return (None if ev is None
            else 100.0 * unattributed_idle_ns(ev) / ev.window_ns)
