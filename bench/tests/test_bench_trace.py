"""The trace reduction on a small synthetic event list: interval union
and idle share, attribution by program and op name, and idle-gap
labels for the breakdown."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.trace import (Event, covered, label_gaps, op_name,  # noqa
                         reduce, union)

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(name, start, dur, line="XLA Ops", plane=DEV):
    return Event(plane, line, name, float(start), float(dur))


def events():
    return [
        # programs: encode [0, 40), candidates [50, 90)
        ev("jit_encode_queries(7)", 0, 40, line="XLA Modules"),
        ev("jit__device_candidates(9)", 50, 40, line="XLA Modules"),
        # ops: two overlap inside encode, one Pallas kernel inside the
        # candidate program
        ev("fusion.1", 0, 30), ev("fusion.2", 20, 20),
        ev("_plaid_probe_kernel", 50, 30), ev("copy.3", 80, 10),
        # host spans
        ev("bench.window", 0, 100, line="python", plane=HOST),
        ev("bench.encode", 38, 10, line="python", plane=HOST),
        ev("bench.search", 88, 12, line="python", plane=HOST),
        ev("other.span", 40, 10, line="python", plane=HOST),
    ]


def test_union_merges_overlaps():
    assert union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [[0, 4], [5, 10]]
    assert covered([[0, 4], [5, 10]], 2, 7) == 4


def test_busy_and_idle_share():
    s = reduce(events(), (0, 100))
    assert s.n_devices == 1
    assert s.busy_ns == pytest.approx(80)          # [0,40) + [50,90)
    assert s.idle_share == pytest.approx(0.2)
    assert s.window_s == pytest.approx(1e-7)


def test_busy_is_clipped_to_the_window():
    s = reduce(events(), (10, 60))
    assert s.busy_ns == pytest.approx(40)          # [10,40) + [50,60)


def test_attribution_by_program_and_op():
    s = reduce(events(), (0, 100))
    assert s.module_time_s("encode_queries") == pytest.approx(40e-9)
    assert s.module_count("_device_candidates") == 1
    assert s.op_time_s("plaid_probe") == pytest.approx(30e-9)
    assert s.op_time_s("fusion") == pytest.approx(50e-9)


def test_gap_labels_and_breakdown():
    s = reduce(events(), (0, 100), host_prefix="bench.",
               exclude=("bench.window",))
    # gaps: [40, 50) overlaps bench.encode by 8 (other.span is not a
    # bench span); [90, 100) overlaps bench.search by 10
    assert sorted(s.gaps) == [("bench.encode", 10.0), ("bench.search", 10.0)]
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"
    assert bd["device_ops"][0][1] == pytest.approx(30e-9)
    assert len(bd["device_ops"]) == 4
    assert {k for k, _ in bd["idle_gaps"]} == {"bench.encode", "bench.search"}


def test_unlabelled_gap_reads_idle():
    merged = [[0.0, 10.0], [20.0, 30.0]]
    assert label_gaps(merged, 0, 30, []) == [("idle", 10.0)]


def test_no_device_events_reads_no_busy_time():
    host = [e for e in events() if e.plane == HOST]
    s = reduce(host, (0, 100))
    assert s.n_devices == 0 and s.busy_ns == 0 and s.gaps == []


def test_op_name_is_the_instruction_name():
    text = ("%plaid_probe_pallas.1 = f32[32,1,32768]{2,1,0} custom-call("
            "f32[32,32,128]{2,1,0} %copy-done.18)")
    assert op_name(text) == "plaid_probe_pallas.1"
    assert op_name("%fusion.3 = s32[8] fusion(s32[8] %plaid_probe_pallas.1)"
                   ) == "fusion.3"
    assert op_name("jit_encode_queries(7)") == "jit_encode_queries(7)"
