"""CPU rehearsal of the harness: each traffic kind drives its cell for
about a second at a CPU-sized configuration, through the same drivers
the chip runs; a configuration, traffic and metric dropped into a copy
of the benchmark are found by name; the CLI refuses a CPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SMOKE = Path(__file__).resolve().parent / "smoke"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

CELLS = {"smoke.closed": "closed_loop", "smoke.stream": "build_stream"}


def make_root(tmp: Path, metric_cells=()) -> Path:
    """A checkout-like root: a copy of ``bench/`` with the smoke files
    dropped in, and a BENCHMARK.json naming the smoke cells."""
    root = tmp / "root"
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns(
        ".jax_cache", ".scratch", "__pycache__", "tests"))
    shutil.copy(SMOKE / "smoke.json", root / "bench" / "configs")
    for t in CELLS:
        shutil.copy(SMOKE / f"{t}.json", root / "bench" / "traffic")
    (root / "bench" / "metrics" / "dropped_in_batches.py").write_text(
        "def read(x):\n    return float(x['batches']) or None\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": t, "config": "smoke", "traffic": t,
                           "chips": 1, "why": "rehearsal"} for t in CELLS]
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    bench["per_layer"] = [{"name": "dropped_in_batches", "unit": "batches",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving engine", "moves": "qps",
                           "workloads": list(metric_cells)}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), ["smoke.closed"])


@pytest.mark.parametrize("cell,metric", [
    ("smoke.closed", "qps"), ("smoke.stream", "build_docs_per_s")])
def test_traffic_kind_runs_and_is_correct(root, cell, metric):
    line = harness.run_cell(str(root), cell, seed=2**31 + 5, seconds=1.0,
                            trace=False, require_tpu=False)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert list(line)[-1] == "compared"
    json.dumps(line, allow_nan=False)


def test_dropped_in_metric_is_read_in_a_traced_run(root):
    line = harness.run_cell(str(root), "smoke.closed", seed=7, seconds=1.0,
                            trace=True, require_tpu=False)
    assert line["correct"], line["compared"]
    assert line["metrics"]["dropped_in_batches"]["value"] > 0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_cli_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "colbertv2.serve.closed128", "--seed",
                        "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_exits_nonzero_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".scratch"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "colbertv2.serve.closed128", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
