"""Independent flop counter: parse dot-general ops out of optimized HLO.

Cross-checks XLA's cost_analysis (which undercounts while-loop bodies —
they are counted ONCE regardless of trip count). Summing every dot in a
module without loops is exact for matmul flops (elementwise flops are
negligible for these workloads).

Handles: plain `dot(...)` ops and dots inside fusion computations (each
fusion is called once per op that references it — we count call sites).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Tuple

_DTYPE = r"(?:f64|f32|f16|bf16|f8e4m3|f8e5m2|s32|u32|s8|u8|pred)"
_SHAPE = rf"{_DTYPE}\[([0-9,]*)\]"

_DOT_RE = re.compile(
    rf"%?[\w.\-]+ = {_SHAPE}[^=]*? dot\(([^)]*)\)(.*)$")
_DIMS_RE = re.compile(
    r"lhs_contracting_dims=\{([0-9,]*)\}")
_BATCH_RE = re.compile(
    r"lhs_batch_dims=\{([0-9,]*)\}")
_OPERAND_SHAPE_RE = re.compile(rf"{_DTYPE}\[([0-9,]*)\]")


def _dims(s: str):
    return [int(x) for x in s.split(",") if x] if s else []


def dot_flops_in_hlo(hlo_text: str) -> Dict:
    """Sum 2*M*N*K flops over every dot in the module.

    Returns {"total": flops, "by_shape": {shape_sig: (count, flops)}}.
    """
    total = 0.0
    by_shape = defaultdict(lambda: [0, 0.0])
    for line in hlo_text.splitlines():
        s = line.strip()
        if " dot(" not in s:
            continue
        m = _DOT_RE.search(s)
        if not m:
            continue
        out_dims = _dims(m.group(1))
        rest = m.group(3)
        cm = _DIMS_RE.search(rest)
        contract = _dims(cm.group(1)) if cm else []
        # operand shapes appear in the operand list annotations; fall back
        # to: flops = 2 * prod(out_dims) * prod(contract sizes of lhs).
        ops = _OPERAND_SHAPE_RE.findall(m.group(2))
        k = 1
        if ops:
            lhs = _dims(ops[0])
            for c in contract:
                if c < len(lhs):
                    k *= lhs[c]
        n_out = 1
        for d in out_dims:
            n_out *= d
        fl = 2.0 * n_out * k
        total += fl
        sig = f"out[{','.join(map(str, out_dims))}]xk{k}"
        by_shape[sig][0] += 1
        by_shape[sig][1] += fl
    top = sorted(by_shape.items(), key=lambda kv: -kv[1][1])[:12]
    return {"total": total,
            "top": [{"shape": s, "count": c, "flops": f}
                    for s, (c, f) in top]}
