"""Benchmark orchestrator — one module per paper table.

    PYTHONPATH=src python -m benchmarks.run            # all tables
    PYTHONPATH=src python -m benchmarks.run --only table1
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["table1", "table2", "table3", "table4",
                             "quality", "kernels"])
    args = ap.parse_args(argv)

    from benchmarks import (kernel_bench, quality_bench,
                            table1_unquantized, table2_quantized,
                            table3_index_size, table4_second_model)
    jobs = {
        "table1": ("Table 1: unquantized (16-bit HNSW)",
                   table1_unquantized.run),
        "table2": ("Table 2: quantized (2-bit PLAID)",
                   table2_quantized.run),
        "table3": ("Table 3: vector count & index size",
                   table3_index_size.run),
        "table4": ("Table 4: second model / language",
                   table4_second_model.run),
        "quality": ("Quality sweep (pool_factor x method x backend)",
                    quality_bench.run),
        "kernels": ("Kernel analysis", kernel_bench.run),
    }
    selected = [args.only] if args.only else list(jobs)
    t00 = time.time()
    for key in selected:
        title, fn = jobs[key]
        print(f"\n{'='*72}\n{title}\n{'='*72}")
        t0 = time.time()
        fn(verbose=False)
        print(f"[{key} done in {time.time()-t0:.0f}s]")
    print(f"\nAll benchmarks done in {time.time()-t00:.0f}s")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
