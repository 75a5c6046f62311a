"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chips
the cell asks for. The last line of stdout is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``); the numbers compared for ``correct``
come last, there and on stderr. Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when
the program under test (``src/repro``) is not in the checkout.

``--control float8_e4m3fn`` runs the cell as usual but judges the
reference computed in that lower precision in the program's place: the
control, whose readings must come out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="judge the reference computed in this dtype "
                         "(e.g. float8_e4m3fn) in the program's place")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run: the program under test is not in this checkout "
              f"({os.path.join(ROOT, 'src', 'repro')})", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    opts = {}
    if args.control:
        opts["control"] = args.control
    try:
        line = harness.run_cell(ROOT, args.workload, args.seed,
                                args.seconds, bool(args.trace),
                                options=opts, t_start=T0)
    except harness.NoChip as e:
        print(f"run: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
