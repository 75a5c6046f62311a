"""The long-doc build cell and the open-loop serve cell at CPU sizes:
both run and come out correct on sound runs; the long-doc cell's
control (its reference in float8) and faults planted in its timed path
come out not correct; the banded work counts and the reference's Ward
agree with brute force and with the plain clustering."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
SMOKE = Path(__file__).resolve().parent / "smoke"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import harness, work, work_band  # noqa: E402
from bench.refs import colbert as colbert_ref  # noqa: E402
from bench.refs import moderncolbert as ref  # noqa: E402
from test_bench_correct import _alter_doc_token, _halve_docs  # noqa: E402

CELLS = {"smoke.long": "smoke-modern", "smoke.steady": "smoke"}
LONG_METRICS = ["pad_pct.long2k", "idle_pct.long2k",
                "doc_encoder_ms_per_kdoc.long2k"]
# serve metrics whose readers find something off the chip too
STEADY_METRICS = ["batch_fill_pct.closed128", "staged_wait_ms.closed128"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench") / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(
                        ".jax_cache", ".scratch", "__pycache__", "tests"))
    for name in ("smoke.json", "smoke-modern.json"):
        shutil.copy(SMOKE / name, root / "bench" / "configs")
    for t in CELLS:
        shutil.copy(SMOKE / f"{t}.json", root / "bench" / "traffic")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": t, "config": c, "traffic": t, "chips": 1,
                           "why": "rehearsal"} for t, c in CELLS.items()]
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    bench["per_layer"] = (
        [dict(m, workloads=["smoke.long"]) for m in bench["per_layer"]
         if m["name"] in LONG_METRICS]
        + [dict(m, workloads=["smoke.steady"]) for m in bench["per_layer"]
           if m["name"] in STEADY_METRICS])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, cell, seed=5, trace=False, **opts):
    line = harness.run_cell(str(root), cell, seed=seed, seconds=1.0,
                            trace=trace, require_tpu=False, options=opts)
    return line, {k: v["ok"] for k, v in line["compared"].items()}


@pytest.mark.parametrize("cell,metric", [("smoke.long", "build_docs_per_s"),
                                         ("smoke.steady", "qps")])
def test_new_cell_runs_and_is_correct(root, cell, metric):
    line, _ = run(root, cell, seed=2**33 + 3)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def test_long_cell_traced_reads_its_metrics(root):
    line, _ = run(root, "smoke.long", seed=11, trace=True)
    assert line["correct"], line["compared"]
    # encode batches of 4 docs, each 40 slots: [CLS][D] and 8-38 tokens
    assert 0 < line["metrics"]["pad_pct.long2k"]["value"] < 60


def test_steady_cell_traced_reads_the_serve_metrics(root):
    """The open loop fills batches only partly and its lane waits on
    staged batches: the serve readers it is listed under read both."""
    line, _ = run(root, "smoke.steady", seed=2**31 + 7, trace=True)
    assert line["correct"], line["compared"]
    assert 0 < line["metrics"]["batch_fill_pct.closed128"]["value"] <= 100
    assert line["metrics"]["staged_wait_ms.closed128"]["value"] >= 0


def test_long_control_is_not_correct(root):
    line, ok = run(root, "smoke.long", control="float8_e4m3fn")
    assert not line["correct"] and not ok["doc_cos_gap"], line["compared"]


@pytest.mark.parametrize("fault", ["halve", "alter_token"])
def test_long_fault_is_not_correct(root, monkeypatch, fault):
    from repro.retrieval import indexer
    if fault == "halve":
        monkeypatch.setattr(indexer.Indexer, "encode_and_pool_counted",
                            _halve_docs(
                                indexer.Indexer.encode_and_pool_counted))
    else:
        monkeypatch.setattr(indexer, "encode_docs",
                            _alter_doc_token(indexer.encode_docs))
    line, _ = run(root, "smoke.long")
    assert not line["correct"], line["compared"]


@pytest.mark.parametrize("L,w", [(1, 64), (5, 64), (64, 64), (65, 64),
                                 (200, 64), (2048, 64), (40, 8)])
def test_band_pairs_match_brute_force(L, w):
    i = np.arange(L)
    want = int((np.abs(i[:, None] - i[None, :]) <= w).sum())
    assert work_band.band_pairs(L, w) == want


def test_banded_flops_without_local_layers_are_the_dense_count():
    trunk = {"n_layers": 12, "d_model": 768, "d_ff": 3072,
             "global_every": 1, "local_window": 64}
    lens = [256, 100, 17]
    dense = work.encoder_flops(12, 768, 3072, 128, lens)
    linear = 2.0 * work.encoder_params(12, 768, 3072, 128) * sum(lens)
    assert work_band.attention_flops(trunk, lens) == dense - linear


def test_banded_flops_at_published_widths():
    """Per 2048-token doc: linears 452 GFLOP, the 8 global layers 103,
    the 14 local layers on the band about 11 (180 if computed dense)."""
    m = json.loads((ROOT / "bench/configs/gte-moderncolbert.json")
                   .read_text())["model"]
    tr = m["trunk"]
    att = work_band.attention_flops(tr, [2048])
    linear = work_band.encoder_flops(m, [2048]) - att
    glob = 8 * 4.0 * 768 * 2048 ** 2
    assert abs(linear / 1e9 - 452) < 1.0
    assert abs(glob / 1e9 - 103) < 1.0
    assert abs((att - glob) / 1e9 - 11) < 1.0


@pytest.mark.parametrize("n,factor", [(5, 2), (40, 2), (97, 3), (300, 2)])
def test_reference_ward_is_the_plain_clustering(n, factor):
    rng = np.random.default_rng(n)
    x = rng.normal(size=(n, 16))
    x[n // 2] = x[1]                  # a tie
    a, b = ref.ward(x, factor), colbert_ref.ward(x, factor)
    assert a.shape == b.shape and np.array_equal(a, b)
