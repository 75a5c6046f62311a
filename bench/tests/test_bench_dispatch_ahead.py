"""The reader of ``dispatch_ahead_pct.closed128`` on synthetic serve
windows: every batch dispatched ahead, none, some, and a window with no
``repro.engine.search`` span (or only spans without the ``ahead`` arg,
as a program without dispatch-ahead writes them)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spans  # noqa: E402
from bench.spans import Events, Span  # noqa: E402

NAME = "dispatch_ahead_pct.closed128"


def window(aheads):
    """One ``repro.engine.search`` span a batch, each with its ``ahead``
    arg (None: the arg left out), among the serve spans that carry no
    such arg."""
    out = []
    for b, a in enumerate(aheads):
        args = {"batch": b, "replica": 0, "staged_wait_us": 100}
        if a is not None:
            args["ahead"] = a
        out += [Span("repro.engine.encode", 10 * b, 10 * b + 2,
                     {"batch": b, "n": 32, "bucket": 32}),
                Span("repro.engine.search", 10 * b + 2, 10 * b + 4, args),
                Span("repro.engine.resolve", 10 * b + 4, 10 * b + 9,
                     {"batch": b})]
    return Events((0.0, 10.0 * max(len(aheads), 1)), [], out)


def read(monkeypatch, ev):
    monkeypatch.setattr(spans, "events", lambda: ev)
    reader = harness.load_module(str(ROOT / "bench" / "metrics"
                                     / f"{NAME}.py"))
    return reader.read({})


@pytest.mark.parametrize("aheads,want", [
    ([1, 1, 1, 1], 100.0),
    ([0, 0, 0], 0.0),
    ([0, 1, 1, 0], 50.0),
    ([0] + [1] * 7, 87.5),
])
def test_reads_the_share_of_batches_dispatched_ahead(monkeypatch, aheads,
                                                     want):
    assert read(monkeypatch, window(aheads)) == pytest.approx(want)


@pytest.mark.parametrize("ev", [None, window([]), window([None, None])],
                         ids=["no_trace", "no_search_span", "no_ahead_arg"])
def test_reads_nothing_without_the_ahead_arg(monkeypatch, ev):
    assert read(monkeypatch, ev) is None


def test_traced_rehearsal_reads_the_ahead_arg(tmp_path):
    """The smoke closed-loop cell run traced on the CPU: the reader
    finds the program's ``ahead`` args and reads a share."""
    import json
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_bench_rehearsal import make_root
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": NAME, "unit": "%", "better": "higher",
                           "source": "program_span",
                           "layer": "serving engine", "moves": "qps",
                           "workloads": ["smoke.closed"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = harness.run_cell(str(root), "smoke.closed", seed=2**31 + 13,
                            seconds=1.0, trace=True, require_tpu=False)
    assert line["correct"], line["compared"]
    assert 0.0 <= line["metrics"][NAME]["value"] <= 100.0
