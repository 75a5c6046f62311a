"""``correct`` comes out false where it should: for the control (the
reference computed in float8 in the program's place) and for faults
planted in the timed path, at the CPU-sized configuration. Each run
skips the harness's look for a chip and drives the rest of a run."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import harness  # noqa: E402
from test_bench_rehearsal import make_root  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def run(root, cell, seed=5, **opts):
    line = harness.run_cell(str(root), cell, seed=seed, seconds=1.0,
                            trace=False, require_tpu=False, options=opts)
    return line, {k: v["ok"] for k, v in line["compared"].items()}


@pytest.mark.parametrize("cell,number", [("smoke.closed", "query_cos_gap"),
                                         ("smoke.stream", "doc_cos_gap")])
def test_control_is_not_correct(root, cell, number):
    line, ok = run(root, cell, control="float8_e4m3fn")
    assert not line["correct"] and not ok[number], line["compared"]


def _halve(search):
    """Half of each batch left out: rows past the middle get row 0's
    answer."""
    def search_batch(self, qs, k=10, **kw):
        S, I = search(self, qs, k=k, **kw)
        S, I = np.array(S), np.array(I)
        h = (len(I) + 1) // 2
        S[h:], I[h:] = S[0], I[0]
        return S, I
    return search_batch


def _alter_ids(search):
    """An answer altered where it is produced: ids off by one."""
    def search_batch(self, qs, k=10, **kw):
        S, I = search(self, qs, k=k, **kw)
        I = np.array(I)
        n = self.n_docs
        return S, np.where(I >= 0, (I + 1) % n, I)
    return search_batch


def _alter_query(encode):
    """A token altered where it is produced: one query vector flipped."""
    def encode_queries(self, toks):
        v = np.array(encode(self, toks))
        v[:, 3] = -v[:, 3]
        return v
    return encode_queries


@pytest.mark.parametrize("fault", ["halve", "alter_ids", "alter_query"])
def test_serve_fault_is_not_correct(root, monkeypatch, fault):
    from repro.core.index import MultiVectorIndex
    from repro.retrieval.searcher import Searcher
    if fault == "alter_query":
        monkeypatch.setattr(Searcher, "encode_queries",
                            _alter_query(Searcher.encode_queries))
    else:
        wrap = _halve if fault == "halve" else _alter_ids
        monkeypatch.setattr(MultiVectorIndex, "search_batch",
                            wrap(MultiVectorIndex.search_batch))
    line, _ = run(root, "smoke.closed")
    assert not line["correct"], line["compared"]


def _halve_docs(pool):
    """Half of each encode batch left out: its second half stores the
    first half's pooled docs."""
    def encode_and_pool_counted(self, toks):
        docs, raw = pool(self, toks)
        h = (len(docs) + 1) // 2
        return docs[:h] + docs[:len(docs) - h], raw
    return encode_and_pool_counted


def _alter_doc_token(encode):
    """A token altered where it is produced: one doc vector flipped."""
    def encode_docs(params, toks, cfg):
        v, emit = encode(params, toks, cfg)
        return v.at[:, 2].multiply(-1.0), emit
    return encode_docs


@pytest.mark.parametrize("fault", ["halve", "alter_token"])
def test_build_fault_is_not_correct(root, monkeypatch, fault):
    from repro.retrieval import indexer
    if fault == "halve":
        monkeypatch.setattr(indexer.Indexer, "encode_and_pool_counted",
                            _halve_docs(
                                indexer.Indexer.encode_and_pool_counted))
    else:
        monkeypatch.setattr(indexer, "encode_docs",
                            _alter_doc_token(indexer.encode_docs))
    line, _ = run(root, "smoke.stream")
    assert not line["correct"], line["compared"]


@pytest.mark.parametrize("cell", ["smoke.closed", "smoke.stream"])
def test_sound_runs_are_correct(root, cell):
    line, ok = run(root, cell, seed=2**33 + 1)
    assert line["correct"], line["compared"]
