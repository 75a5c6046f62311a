"""The reductions of ``bench/spans.py`` on a synthetic window (device
idle time under spans and under none, arg sums, device time under a
named scope), each reader of the program's spans on a synthetic input
and on a window without them, and a traced CPU rehearsal of the smoke
cells that reads the transfer metrics and the staged wait from the
program's own spans."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import harness, spans  # noqa: E402
from bench.spans import Events, Span  # noqa: E402
from test_bench_rehearsal import make_root  # noqa: E402

ENC = "jit(encode_docs)/encoder/while/body/closed_call"


def build_window():
    """Window [0, 100): the device busy on [0, 40) and [60, 90), idle on
    [40, 60) and [90, 100); the flush thread's shard span overlaps the
    build thread's spans."""
    return Events((0.0, 100.0), [[0.0, 40.0], [60.0, 90.0]], [
        Span("repro.indexer.fetch", 35, 55, {"batch": 0, "d2h_bytes": 100}),
        Span("repro.indexer.encode", 55, 62,
             {"batch": 1, "docs": 4, "h2d_bytes": 10}),
        Span("repro.indexer.pool", 62, 64, {"batch": 1}),
        Span("repro.indexer.shard", 50, 95, {"shard": 0}),
        Span("repro.plaid.add", 52, 58, {"h2d_bytes": 5, "d2h_bytes": 1}),
    ], [(f"{ENC}/attention/dot_general", 0, 20),
        (f"{ENC}/attention/add", 10, 25),
        (f"{ENC}/mlp/dot_general", 25, 40),
        ("jit(_device_candidates)/candidates/prune/cond/x", 60, 90)])


def serve_window():
    """Two served batches; the device idle on [20, 30) and [80, 100)."""
    return Events((0.0, 100.0), [[0.0, 20.0], [30.0, 80.0]], [
        Span("repro.engine.encode", 0, 10, {"batch": 0, "n": 3,
                                           "bucket": 4}),
        Span("repro.encoder.queries", 1, 9, {"h2d_bytes": 40,
                                             "d2h_bytes": 400}),
        Span("repro.engine.search", 15, 35, {"batch": 0,
                                             "staged_wait_us": 2000}),
        Span("repro.plaid.candidates", 16, 25, {"path": "device",
                                                "h2d_bytes": 800,
                                                "d2h_bytes": 0}),
        Span("repro.plaid.rerank", 25, 30, {"h2d_bytes": 400}),
        Span("repro.plaid.topk", 30, 34, {"d2h_bytes": 40}),
        Span("repro.engine.encode", 40, 45, {"batch": 1, "n": 1,
                                            "bucket": 1}),
        Span("repro.engine.search", 50, 90, {"batch": 1,
                                             "staged_wait_us": 4000}),
    ])


def test_idle_under_spans_and_under_none():
    ev = build_window()
    assert spans.idle_under_ns(ev, "repro.indexer.fetch") == 15
    assert spans.idle_under_ns(ev, "repro.indexer.encode",
                               "repro.indexer.pool") == 5
    # idle 30; the spans' union [35, 95) covers all of it but [95, 100)
    assert spans.unattributed_idle_ns(ev) == 5
    assert spans.idle_under_ns(ev, "repro.no.such") == 0


def test_spans_are_clipped_to_the_window():
    ev = build_window()
    ev.spans.append(Span("repro.indexer.input", 98, 130, {"batch": 2}))
    assert spans.idle_under_ns(ev, "repro.indexer.input") == 2
    assert spans.unattributed_idle_ns(ev) == 3


def test_arg_sums_and_values():
    ev = build_window()
    assert spans.arg_sum(ev, *spans.BYTES) == 116
    assert spans.arg_values(ev, "batch", "repro.indexer.encode",
                            "repro.indexer.pool") == [1, 1]
    assert spans.arg_sum(ev, "no_such_arg") is None


def test_scope_time_and_paths():
    ev = build_window()
    assert spans.scope_time_ns(ev, "encoder/attention") == 25   # union
    assert spans.scope_time_ns(ev, "encoder/mlp") == 15
    assert spans.scope_time_ns(ev, "candidates/prune") == 30
    assert spans.in_scope(f"{ENC}/attention/add", "encoder/attention")
    assert not spans.in_scope(f"{ENC}/attention/add", "attention/encoder")
    assert not spans.in_scope("jit(f)/encoder_x/attention", "encoder")
    ev.scoped = []
    assert spans.scope_time_ns(ev, "encoder/attention") is None


def _varint(x):
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _f(num, v):
    """One protobuf field: an int (varint) or bytes/str (delimited)."""
    if isinstance(v, int):
        return _varint(num << 3) + _varint(v)
    v = v.encode() if isinstance(v, str) else v
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _entry(field, key, value):
    return _f(field, _f(1, key) + _f(2, value))


def test_op_scopes_read_from_event_metadata(tmp_path):
    """The scope is the ``tf_op`` stat of an op's event metadata, held
    as a string or as a reference to a stat metadata name."""
    attn = "jit(f)/encoder/while/body/attention/dot_general:"
    dev = (_f(1, 3) + _f(2, "/device:TPU:0")
           + _f(3, _f(2, "XLA Ops") + _f(4, _f(1, 1) + _f(3, 5)))
           + _entry(4, 1, _f(1, 1) + _f(2, "%fusion.1 = f32[8] fusion()")
                    + _f(4, "fusion.1") + _f(5, _f(1, 7) + _f(5, attn))
                    + _f(5, _f(1, 8) + _f(4, 12)))
           + _entry(4, 2, _f(1, 2) + _f(2, "%copy.2 = f32[8] copy()")
                    + _f(5, _f(1, 7) + _f(7, 9)))
           + _entry(4, 3, _f(1, 3) + _f(2, "%while = () while()"))
           + _entry(5, 7, _f(1, 7) + _f(2, "tf_op"))
           + _entry(5, 8, _f(1, 8) + _f(2, "flops"))
           + _entry(5, 9, _f(1, 9) + _f(2, "jit(f)/encoder/mlp/add:")))
    host = (_f(2, "/host:CPU")
            + _entry(4, 1, _f(2, "repro.x") + _f(5, _f(1, 7) + _f(5, "h"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, host) + _f(1, dev) + _f(4, "host0"))
    assert spans.op_scopes(str(path)) == {
        "%fusion.1 = f32[8] fusion()": attn, "fusion.1": attn,
        "%copy.2 = f32[8] copy()": "jit(f)/encoder/mlp/add:"}
    assert spans.in_scope(attn, "encoder/attention")
    path.write_bytes(b"\xff\xff\xff")
    assert spans.op_scopes(str(path)) == {}


def reader(name):
    return harness.load_module(str(ROOT / "bench" / "metrics"
                                   / f"{name}.py"))


@pytest.mark.parametrize("name,window,want", [
    ("idle_fetch_pct.build", build_window, 15.0),
    ("idle_dispatch_pct.build", build_window, 5.0),
    ("idle_unattributed_pct.build", build_window, 5.0),
    ("transfer_bytes_per_doc.build", build_window, 116 / 8),
    ("attention_ms_per_kdoc.build", build_window, 25e-9 * 1e6 / 8),
    ("idle_search_pct.closed128", serve_window, 20.0),
    ("idle_unattributed_pct.closed128", serve_window, 10.0),
    ("staged_wait_ms.closed128", serve_window, 3.0),
    ("transfer_bytes_per_query.closed128", serve_window, 1680 / 4),
])
def test_reader_reads_its_number(monkeypatch, name, window, want):
    monkeypatch.setattr(spans, "events", window)
    assert reader(name).read({"docs": 8}) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "idle_fetch_pct.build", "idle_dispatch_pct.build",
    "idle_unattributed_pct.build", "transfer_bytes_per_doc.build",
    "attention_ms_per_kdoc.build", "idle_search_pct.closed128",
    "idle_unattributed_pct.closed128", "staged_wait_ms.closed128",
    "transfer_bytes_per_query.closed128"])
def test_reader_reads_nothing_without_the_programs_spans(monkeypatch,
                                                         name):
    """A program without ``repro.`` spans (no events), or a window that
    lacks the reader's spans, reads None and does not raise."""
    monkeypatch.setattr(spans, "events", lambda: None)
    assert reader(name).read({"docs": 8}) is None
    # the other cell's window: its spans are not this reader's (idle
    # under no span, and the bytes of a build, read any window)
    other = serve_window if name.endswith(".build") else build_window
    if "unattributed" not in name and "per_doc" not in name:
        monkeypatch.setattr(spans, "events", other)
        assert reader(name).read({"docs": 8}) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The smoke cells with the span readers as their metrics."""
    root = make_root(tmp_path_factory.mktemp("bench"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [
        {"name": name, "unit": "bytes", "better": "lower",
         "source": "program_counter", "layer": "host-device transfers",
         "moves": moves, "workloads": [cell]}
        for name, moves, cell in [
            ("transfer_bytes_per_doc.build", "build_docs_per_s",
             "smoke.stream"),
            ("transfer_bytes_per_query.closed128", "qps", "smoke.closed"),
            ("staged_wait_ms.closed128", "qps", "smoke.closed")]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell,names", [
    ("smoke.stream", ["transfer_bytes_per_doc.build"]),
    ("smoke.closed", ["transfer_bytes_per_query.closed128",
                      "staged_wait_ms.closed128"])])
def test_traced_rehearsal_reads_the_programs_spans(root, cell, names):
    line = harness.run_cell(str(root), cell, seed=2**31 + 11, seconds=1.0,
                            trace=True, require_tpu=False)
    assert line["correct"], line["compared"]
    for name in names:
        assert line["metrics"][name]["value"] >= 0, name
    assert line["metrics"][names[0]]["value"] > 0    # the bytes moved
