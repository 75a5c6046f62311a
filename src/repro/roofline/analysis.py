"""Three-term roofline: compute, HBM and collective time of a program.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

``cost_analysis()`` supplies HLO_FLOPs and HLO_bytes. Collective bytes are
NOT in cost_analysis — we parse the optimized HLO text and sum the operand
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.

Notes on semantics:
  * cost_analysis flops/bytes are PER-PROGRAM (one SPMD replica executes
    the partitioned program), so terms divide by chips only when the HLO
    is the unpartitioned module; XLA's SPMD pipeline reports the
    *partitioned* program — i.e. already per-chip. We therefore treat
    flops/bytes as per-chip and do NOT divide again (validated in tests
    against hand-counted matmuls).
  * collective bytes summed from the partitioned HLO are per-chip traffic.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict

from repro.roofline import hw

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all typed shapes in an HLO result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict:
    """Sum result sizes of every collective op in (optimized) HLO text.

    Returns {"total": bytes, "by_op": {op: {"count": n, "bytes": b}}}.
    Fusion-internal lines are skipped (collectives are never fused).
    """
    by_op = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        # HLO: "%name = TYPE[shape] all-reduce(...)" or "... all-reduce-start"
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start|-done)?\(", s)
        if not m:
            continue
        if m.group(0).find("-done(") >= 0:
            continue                      # count start, not done
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        by_op[op]["count"] += 1
        by_op[op]["bytes"] += b
    total = sum(v["bytes"] for v in by_op.values())
    return {"total": total,
            "by_op": {k: v for k, v in by_op.items() if v["count"]}}


@dataclass
class RooflineTerms:
    arch: str
    cell: str
    mesh: str
    flops: float                  # per-chip HLO flops
    hlo_bytes: float              # per-chip HBM traffic
    collective_bytes: float       # per-chip collective traffic
    model_flops: float = 0.0      # 6*N*D useful flops (whole step, per chip)

    @property
    def compute_s(self) -> float:
        return self.flops / hw.PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / hw.HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / hw.ICI_LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-optimistic step time: max of the three terms (perfect
        overlap of compute, HBM and ICI)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline-optimistic step time."""
        if self.step_time_s == 0:
            return 0.0
        return (self.model_flops / hw.PEAK_FLOPS_BF16) / self.step_time_s

    def row(self) -> str:
        return (f"{self.arch:22s} {self.cell:14s} {self.mesh:9s} "
                f"{self.compute_s:9.4f} {self.memory_s:9.4f} "
                f"{self.collective_s:9.4f} {self.bottleneck:10s} "
                f"{self.useful_flops_frac:6.1%} {self.mfu:6.1%}")


HEADER = (f"{'arch':22s} {'cell':14s} {'mesh':9s} {'compute_s':>9s} "
          f"{'memory_s':>9s} {'collect_s':>9s} {'bottleneck':10s} "
          f"{'useful':>6s} {'mfu':>6s}")
