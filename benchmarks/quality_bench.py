"""Quality sweep + regression gate, as a CLI.

    # full synthetic sweep -> BENCH_quality.json section "quality_sweep"
    PYTHONPATH=src python -m benchmarks.quality_bench

    # CI smoke: small grid, paper-envelope assertion, gate vs a pinned
    # baseline file, non-zero exit on failure
    PYTHONPATH=src python -m benchmarks.quality_bench --smoke \
        --assert-envelope --baseline benchmarks/quality_baseline.json

    # refresh the pinned baseline after a deliberate change
    PYTHONPATH=src python -m benchmarks.quality_bench --smoke \
        --write-baseline benchmarks/quality_baseline.json

A real BEIR corpus drops in via ``--beir <dir>`` (the standard
``corpus.jsonl`` / ``queries.jsonl`` / ``qrels/<split>.tsv`` layout).
"""
from __future__ import annotations

import argparse

from repro.eval import (BENCH_QUALITY_FILE, QualitySweep, load_beir,
                        run_gate, synthetic_dataset,
                        write_bench_section)

SECTION = "quality_sweep"
# the CI smoke grid: both pooling families x the factors the paper
# headlines x both backend families, small corpus for wall-time
SMOKE = dict(dataset="scifact", n_docs=120, n_queries=20,
             methods=("ward", "sequential"), factors=(1, 2, 4),
             backends=("flat", "plaid"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="scifact")
    ap.add_argument("--beir", default=None, metavar="DIR",
                    help="BEIR-format dataset directory (overrides "
                         "--dataset)")
    ap.add_argument("--split", default="test")
    ap.add_argument("--docs", type=int, default=200)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--methods", nargs="+",
                    default=["ward", "sequential"])
    ap.add_argument("--factors", nargs="+", type=int,
                    default=[1, 2, 3, 4])
    ap.add_argument("--backends", nargs="+",
                    default=["flat", "plaid"])
    ap.add_argument("--quant-bits", nargs="+", type=int, default=[2])
    ap.add_argument("--metrics", nargs="+",
                    default=["ndcg@10", "recall@5", "success@5",
                             "mrr@10"])
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ja", action="store_true",
                    help="use the Japanese-analogue bench encoder")
    ap.add_argument("--out", default=BENCH_QUALITY_FILE)
    ap.add_argument("--smoke", action="store_true",
                    help="CI grid: ward/sequential x f 1/2/4 x "
                         "flat/plaid on a small corpus")
    ap.add_argument("--assert-envelope", action="store_true",
                    help="fail (exit 1) when a cell drops below the "
                         "paper envelope")
    ap.add_argument("--min-relative", type=float, default=95.0,
                    help="factor-2 relative floor for the envelope "
                         "gate (default: paper's 95)")
    ap.add_argument("--gate-methods", nargs="+", default=None,
                    help="restrict the envelope gate to these pooling "
                         "methods (default: all swept)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="pinned BENCH_quality.json to gate "
                         "regressions against")
    ap.add_argument("--tolerance", type=float, default=3.0,
                    help="allowed relative-point drop vs the pinned "
                         "baseline (cross-box float drift)")
    ap.add_argument("--write-baseline", default=None, metavar="FILE",
                    help="also write the report to FILE (refresh the "
                         "pin)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.dataset = SMOKE["dataset"]
        args.docs, args.queries = SMOKE["n_docs"], SMOKE["n_queries"]
        args.methods = list(SMOKE["methods"])
        args.factors = list(SMOKE["factors"])
        args.backends = list(SMOKE["backends"])

    from benchmarks.common import bench_encoder
    params, cfg = bench_encoder(ja=args.ja, verbose=False)
    if args.beir:
        dataset = load_beir(args.beir, doc_maxlen=cfg.doc_maxlen - 2,
                            query_maxlen=cfg.query_maxlen - 2,
                            split=args.split,
                            vocab_size=cfg.trunk.vocab_size,
                            max_docs=args.docs or None)
    else:
        dataset = synthetic_dataset(
            args.dataset, vocab_size=cfg.trunk.vocab_size,
            doc_maxlen=cfg.doc_maxlen - 2,
            query_maxlen=cfg.query_maxlen - 2,
            n_docs=args.docs, n_queries=args.queries)

    report = QualitySweep(
        params, cfg, dataset, methods=args.methods,
        factors=args.factors, backends=args.backends,
        quant_bits=args.quant_bits, metrics=args.metrics,
        k=args.k).run(verbose=True)

    print()
    print(report.summary(args.metrics[0]))
    for backend in args.backends:
        for qb in (args.quant_bits if backend == "plaid" else [None]):
            print()
            print(report.markdown_table(args.metrics[0],
                                        backend=backend,
                                        quant_bits=qb))
    write_bench_section(args.out, SECTION, report)
    print(f"\nwrote section {SECTION!r} -> {args.out}")
    if args.write_baseline:
        write_bench_section(args.write_baseline, SECTION, report)
        print(f"pinned baseline -> {args.write_baseline}")

    if args.assert_envelope or args.baseline:
        gate = run_gate(
            report, metric=args.metrics[0],
            baseline_path=args.baseline,
            baseline_section=SECTION,
            methods=args.gate_methods,
            min_relative=args.min_relative if args.assert_envelope
            else None,
            tolerance=args.tolerance)
        print(f"\ngate: {gate.summary()}")
        if not gate.ok:
            return 1
    return 0


def run(verbose: bool = True):
    """Orchestrator entry point (benchmarks.run)."""
    return main([])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
