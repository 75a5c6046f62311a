"""Concurrent serving runtime (launch/engine.py): dynamic batcher
parity, shape-bucket no-retrace, hot swap under load, and the
thread-safety contracts on ShardedIndex probe stats.

Parity regime follows the sharded contract (tests/test_sharded.py):
exhaustive candidate budgets, dim=16, so results are BITWISE equal —
np.array_equal on scores AND ids, not allclose.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.index import MultiVectorIndex
from repro.core.sharded import ShardedIndex
from repro.launch.engine import (CompileCounter, IndexHandle,
                                 ServingEngine, bucket_for, run_open_loop,
                                 shape_buckets)

DIM = 16
LQ = 5
BACKENDS = ["flat", "hnsw", "plaid"]


def unit_docs(rng, n=40, dim=DIM, lo=4, hi=20):
    docs = []
    for _ in range(n):
        v = rng.normal(size=(rng.integers(lo, hi), dim)).astype(np.float32)
        docs.append(v / np.linalg.norm(v, axis=-1, keepdims=True))
    return docs


def unit_queries(rng, n, lq=LQ, dim=DIM):
    q = rng.normal(size=(n, lq, dim)).astype(np.float32)
    return q / np.linalg.norm(q, axis=-1, keepdims=True)


def make_index(backend, sharded=False, n_docs=40, seed=0):
    """Exhaustive-candidate regime: engine results must be bitwise equal
    to direct search, so stage 1 must never prune."""
    rng = np.random.default_rng(seed)
    docs = unit_docs(rng, n=n_docs)
    kw = dict(doc_maxlen=24, n_centroids=8, nprobe=8, ndocs=4096,
              hnsw_candidates=4096)
    if sharded:
        idx = ShardedIndex(dim=DIM, backend=backend,
                           shard_max_vectors=(len(docs) // 3) * 12, **kw)
    else:
        idx = MultiVectorIndex(dim=DIM, backend=backend, **kw)
    idx.add(docs)
    return idx


class VecSearcher:
    """Minimal two-stage searcher for engine tests: 'token' arrays are
    already query vectors, so encode is the identity — engine behavior
    (coalescing, padding, swap) is isolated from the encoder."""

    def __init__(self, index):
        self.index = index

    def encode_queries(self, q):
        return np.asarray(q, np.float32)

    def warmup(self, batch_sizes, k=10):
        if isinstance(batch_sizes, (int, np.integer)):
            batch_sizes = [batch_sizes]
        for bs in sorted(set(batch_sizes)):
            self.index.search_batch(
                np.zeros((bs, LQ, DIM), np.float32), k=k)


# ---------------------------------------------------------------- buckets
def test_shape_buckets():
    assert shape_buckets(32) == [1, 2, 4, 8, 16, 32]
    assert shape_buckets(12) == [1, 2, 4, 8, 12]
    assert shape_buckets(1) == [1]
    assert bucket_for(5, [1, 2, 4, 8]) == 8
    assert bucket_for(8, [1, 2, 4, 8]) == 8
    with pytest.raises(ValueError):
        bucket_for(9, [1, 2, 4, 8])


# ---------------------------------------------------------------- parity
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sharded", [False, True],
                         ids=["monolithic", "sharded"])
def test_engine_parity_coalesced_padded(backend, sharded):
    """Every request served through the batcher — coalesced with others,
    zero-padded to a shape bucket, split across microbatches — returns
    BITWISE the result of a direct search_batch call."""
    rng = np.random.default_rng(1)
    idx = make_index(backend, sharded=sharded)
    qs = unit_queries(rng, 30)
    S_ref, I_ref = idx.search_batch(qs, k=6)

    with ServingEngine(VecSearcher(idx), max_batch=8, max_wait_ms=2.0,
                       k=6) as eng:
        # mixed request sizes (1..5 queries), all in flight at once
        reqs, futs = [], []
        lo = 0
        sizes = [1, 3, 1, 5, 2, 4, 1, 1, 3, 2, 5, 2]
        for n in sizes:
            reqs.append((lo, n))
            futs.append(eng.submit(qs[lo:lo + n]))
            lo += n
        assert lo == 30
        for (lo, n), fut in zip(reqs, futs):
            S, I = fut.result(timeout=30)
            assert np.array_equal(S, S_ref[lo:lo + n]), (backend, lo)
            assert np.array_equal(I, I_ref[lo:lo + n]), (backend, lo)
    snap = eng.stats.snapshot()
    assert snap["served"] == 30 and snap["failed"] == 0


def test_engine_request_spans_microbatches():
    """A request bigger than max_batch is sliced across several
    microbatches and reassembled in order."""
    rng = np.random.default_rng(2)
    idx = make_index("flat")
    qs = unit_queries(rng, 20)
    S_ref, I_ref = idx.search_batch(qs, k=5)
    with ServingEngine(VecSearcher(idx), max_batch=8, max_wait_ms=1.0,
                       k=5) as eng:
        S, I = eng.submit(qs).result(timeout=30)
    assert np.array_equal(S, S_ref) and np.array_equal(I, I_ref)
    assert eng.stats.snapshot()["batches"] >= 3     # 20 queries / cap 8


def test_engine_k_switch_flush():
    """Requests with different k never share a microbatch; both ks get
    correct (bitwise) results and the flush reason is recorded."""
    rng = np.random.default_rng(3)
    idx = make_index("flat")
    qs = unit_queries(rng, 8)
    S4, I4 = idx.search_batch(qs, k=4)
    S9, I9 = idx.search_batch(qs, k=9)
    with ServingEngine(VecSearcher(idx), max_batch=8, max_wait_ms=20.0,
                       k=4) as eng:
        futs = [eng.submit(qs[i][None], k=(4 if i % 2 == 0 else 9))
                for i in range(8)]
        for i, fut in enumerate(futs):
            S, I = fut.result(timeout=30)
            Sr, Ir = (S4, I4) if i % 2 == 0 else (S9, I9)
            assert np.array_equal(S[0], Sr[i]) and np.array_equal(I[0], Ir[i])
    assert eng.stats.snapshot()["flush_reasons"]["k_switch"] >= 1


def test_engine_concurrent_submitters_parity():
    """Many threads submitting concurrently: no drops, no cross-request
    leakage, every result bitwise-correct."""
    rng = np.random.default_rng(4)
    idx = make_index("flat", n_docs=50)
    qs = unit_queries(rng, 48)
    S_ref, I_ref = idx.search_batch(qs, k=6)
    errors = []

    with ServingEngine(VecSearcher(idx), max_batch=8, max_wait_ms=1.0,
                       k=6) as eng:
        def worker(base):
            try:
                for j in range(base, base + 12, 3):
                    n = min(3, 48 - j)
                    S, I = eng.submit(qs[j:j + n]).result(timeout=30)
                    assert np.array_equal(S, S_ref[j:j + n])
                    assert np.array_equal(I, I_ref[j:j + n])
            except BaseException as e:          # noqa: BLE001
                errors.append(e)
        threads = [threading.Thread(target=worker, args=(b,))
                   for b in (0, 12, 24, 36)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    snap = eng.stats.snapshot()
    assert snap["served"] == 48 and snap["failed"] == 0


def test_engine_interleaving_property():
    """Hypothesis: ANY interleaving of concurrent submits preserves
    per-request results vs a solo search_batch — no drops, no
    cross-request leakage, correct unpadding."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    rng = np.random.default_rng(5)
    idx = make_index("flat", n_docs=40)
    pool = unit_queries(rng, 32)
    S_ref, I_ref = idx.search_batch(pool, k=5)
    searcher = VecSearcher(idx)

    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 27), st.integers(1, 5),
                              st.integers(0, 2)),
                    min_size=1, max_size=10))
    def run(requests):
        with ServingEngine(searcher, max_batch=8, max_wait_ms=1.0,
                           k=5, warmup_on_start=False) as eng:
            futs = []
            def submit_some(rs):
                for lo, n, jitter in rs:
                    if jitter:
                        time.sleep(jitter * 1e-3)
                    futs.append((lo, n, eng.submit(pool[lo:lo + n])))
            half = len(requests) // 2
            t = threading.Thread(target=submit_some,
                                 args=(requests[half:],))
            t.start()
            submit_some(requests[:half])
            t.join()
            for lo, n, fut in futs:
                S, I = fut.result(timeout=30)
                assert S.shape == (n, 5) and I.shape == (n, 5)
                assert np.array_equal(S, S_ref[lo:lo + n])
                assert np.array_equal(I, I_ref[lo:lo + n])
            assert len(futs) == len(requests)

    run()


# --------------------------------------------------------------- hot swap
def test_index_handle_drains_before_retire():
    retired = []
    h = IndexHandle("idx", generation=1, on_retire=retired.append)
    h.acquire()
    h.acquire()
    h.retire()
    assert not retired                  # two readers still inside
    h.release()
    assert not retired
    h.release()
    assert retired == [h]               # fired exactly at drain
    assert h.wait_drained(0.1)


def test_engine_swap_index_in_flight_parity():
    """Direct swap while requests are in flight: old handle drains, new
    generation serves, zero failures, results stay bitwise-correct."""
    rng = np.random.default_rng(6)
    idx_a = make_index("flat", seed=6)
    idx_b = make_index("flat", seed=6)      # identical twin
    qs = unit_queries(rng, 32)
    S_ref, I_ref = idx_a.search_batch(qs, k=5)

    with ServingEngine(VecSearcher(idx_a), max_batch=4,
                       max_wait_ms=1.0, k=5) as eng:
        futs = [eng.submit(qs[i][None]) for i in range(16)]
        old = eng.swap_index(idx_b)
        futs += [eng.submit(qs[i][None]) for i in range(16, 32)]
        for i, fut in enumerate(futs):
            S, I = fut.result(timeout=30)
            assert np.array_equal(S[0], S_ref[i])
            assert np.array_equal(I[0], I_ref[i])
        assert old.wait_drained(timeout=10)
    snap = eng.stats.snapshot()
    assert snap["failed"] == 0 and snap["swaps"] == 1
    gens = snap["generations_seen"]
    assert all(a <= b for a, b in zip(gens, gens[1:]))
    assert eng.generation == 1


def test_engine_hot_swap_under_load(tmp_path):
    """Watcher-driven swap with concurrent traffic: republishing the
    artifact bumps the generation, the engine swaps mid-stream, and NO
    query fails or returns a wrong result."""
    from repro.core.persist import save_index

    rng = np.random.default_rng(7)
    idx = make_index("plaid", seed=7)
    qs = unit_queries(rng, 24)
    S_ref, I_ref = idx.search_batch(qs, k=5)
    d = str(tmp_path / "artifact")
    save_index(idx, d)                       # generation 1

    eng = ServingEngine(VecSearcher(idx), max_batch=8, max_wait_ms=1.0,
                        k=5, index_dir=d, poll_interval_s=0.03)
    eng.start()
    assert eng.generation == 1
    stop = threading.Event()
    errors, mismatches = [], []

    def load():
        j = 0
        while not stop.is_set():
            i = j % 24
            try:
                S, I = eng.search(qs[i][None], timeout=30)
                if not (np.array_equal(S[0], S_ref[i])
                        and np.array_equal(I[0], I_ref[i])):
                    mismatches.append(i)
            except Exception as e:           # noqa: BLE001
                errors.append(e)
            j += 1

    threads = [threading.Thread(target=load) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    save_index(idx, d)                       # republish -> generation 2
    deadline = time.time() + 15
    while eng.generation < 2 and time.time() < deadline:
        time.sleep(0.02)
    time.sleep(0.3)                          # keep serving post-swap
    stop.set()
    for t in threads:
        t.join()
    eng.stop()

    assert eng.generation == 2, "hot swap not observed"
    assert not errors and not mismatches
    snap = eng.stats.snapshot()
    assert snap["failed"] == 0 and snap["swaps"] == 1
    gens = snap["generations_seen"]
    assert all(a <= b for a, b in zip(gens, gens[1:]))
    assert {1, 2} <= set(gens)               # both generations served


def test_open_loop_driver_zero_errors():
    rng = np.random.default_rng(8)
    idx = make_index("flat")
    qs = unit_queries(rng, 16)
    S_ref, I_ref = idx.search_batch(qs, k=5)
    with ServingEngine(VecSearcher(idx), max_batch=8, max_wait_ms=1.0,
                       k=5) as eng:
        row = run_open_loop(eng, qs, arrival_qps=300.0, n_queries=40,
                            k=5, collect_results=True)
    assert row["errors"] == 0
    for i, (S, I) in enumerate(row["results"]):
        j = i % 16
        assert np.array_equal(S[0], S_ref[j])
        assert np.array_equal(I[0], I_ref[j])


# ---------------------------------------------------- sharded probe stats
def test_sharded_probe_stats_per_call_and_parallel():
    """Per-call probe timings under concurrent batches (no shared-list
    races) and thread-parallel fan-out returning bitwise the sequential
    merge."""
    rng = np.random.default_rng(9)
    idx = make_index("plaid", sharded=True, n_docs=42, seed=9)
    assert idx.n_shards >= 2
    qs = unit_queries(rng, 12)

    idx.probe_threads = 1
    S_seq, I_seq, probe_seq = idx.search_batch_with_stats(qs, k=6)
    assert len(probe_seq) == idx.n_shards
    idx.probe_threads = 4
    S_par, I_par, probe_par = idx.search_batch_with_stats(qs, k=6)
    assert len(probe_par) == idx.n_shards
    assert np.array_equal(S_seq, S_par) and np.array_equal(I_seq, I_par)

    errors = []
    def worker():
        try:
            for _ in range(5):
                S, I, probe = idx.search_batch_with_stats(qs, k=6)
                assert len(probe) == idx.n_shards
                assert all(p >= 0.0 for p in probe)
                assert np.array_equal(S, S_seq)
                assert np.array_equal(I, I_seq)
        except BaseException as e:          # noqa: BLE001
            errors.append(e)
    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    # convenience snapshot still present and complete
    idx.search_batch(qs, k=6)
    assert len(idx.last_probe_s) == idx.n_shards


# ------------------------------------------------------------- no-retrace
@pytest.fixture(scope="module")
def real_searcher():
    """Real encode + flat index on a tiny corpus (flat keeps stage-2
    shapes deterministic, so the compile probe measures only the
    bucket cache)."""
    import jax
    from repro.configs import get_smoke_config
    from repro.data.corpus import DATASET_SPECS, SyntheticRetrievalCorpus
    from repro.models.colbert import init_colbert
    from repro.retrieval.indexer import Indexer
    from repro.retrieval.searcher import Searcher
    from dataclasses import replace

    cfg = get_smoke_config("colbertv2")
    params = init_colbert(jax.random.PRNGKey(0), cfg)
    spec = replace(DATASET_SPECS["scifact"], n_docs=40, n_queries=32)
    corpus = SyntheticRetrievalCorpus(spec, vocab_size=cfg.trunk.vocab_size)
    indexer = Indexer(params, cfg, pool_method="ward", pool_factor=2,
                      backend="flat")
    index, _ = indexer.build(corpus.doc_token_batch(cfg.doc_maxlen - 2))
    return (Searcher(params, cfg, index),
            corpus.query_token_batch(cfg.query_maxlen - 2))


def test_encoder_rows_bitwise_width_stable(real_searcher):
    """The parity contract's foundation: a query's encoded vectors are
    bitwise identical whatever power-of-two width its chunk padded to
    (1-wide, 8-wide, full batch) — so coalescing never changes them."""
    searcher, q_all = real_searcher
    e8 = searcher.encode_queries(q_all[:8])
    e1 = searcher.encode_queries(q_all[:1])
    e3 = searcher.encode_queries(q_all[:3])         # pads to width 4
    assert np.array_equal(e1[0], e8[0])
    assert np.array_equal(e3, e8[:3])


def test_warmup_buckets_no_retrace_mixed_stream(real_searcher):
    """Satellite regression: warming every shape bucket means a mixed
    stream of BUCKET-sized batches compiles NOTHING new (the old warmup
    warmed one size and re-jitted mid-serve on every other), and the
    encoder — which buckets internally — additionally absorbs ARBITRARY
    request sizes without a trace."""
    searcher, q_all = real_searcher
    # probe sanity: a genuinely cold shape (batch 5 / k=9, used nowhere
    # else in this module) MUST register compiles — guards against the
    # jax monitoring event going silent and the 0-assertions below
    # passing vacuously
    with CompileCounter() as cold:
        searcher.search(q_all[:5], k=9)
    assert cold.count > 0, "compile probe is not observing compilations"
    buckets = shape_buckets(8)
    searcher.warmup(buckets, k=10)
    with CompileCounter() as c:
        for bs in (4, 1, 8, 2, 4, 8, 1, 2):     # warmed bucket shapes
            S, I = searcher.search(q_all[:bs], k=10)
            assert S.shape == (bs, 10)
    assert c.count == 0, f"{c.count} re-traces despite bucketed warmup"
    with CompileCounter() as c:
        for bs in (3, 5, 6, 7):                  # odd sizes: encoder pads
            assert len(searcher.encode_queries(q_all[:bs])) == bs
    assert c.count == 0, f"{c.count} encoder re-traces at odd sizes"


def test_engine_no_retrace_after_start(real_searcher):
    """Engine-level version: after start() (which warms the buckets), a
    mixed stream of request sizes triggers zero compilations."""
    searcher, q_all = real_searcher
    with ServingEngine(searcher, max_batch=8, max_wait_ms=1.0,
                       k=10) as eng:
        with CompileCounter() as c:
            futs = [eng.submit(q_all[i:i + n])
                    for i, n in [(0, 3), (3, 1), (4, 5), (9, 2), (11, 8)]]
            for fut in futs:
                fut.result(timeout=60)
        assert c.count == 0, f"{c.count} re-traces in engine stream"
    assert eng.stats.snapshot()["failed"] == 0


def test_serve_microbatches_exact_counts(real_searcher):
    """Satellite regression: n_queries % batch_size != 0 must not wrap
    around and over-serve; per-batch sizes are reported exactly."""
    from repro.launch.serve import serve_microbatches
    searcher, q_all = real_searcher
    lat, sizes = serve_microbatches(searcher, q_all, batch_size=8,
                                    n_queries=19, k=5)
    assert sizes.sum() == 19
    assert list(sizes) == [8, 8, 3]
    assert len(lat) == 3


def test_serve_cli_exits_nonzero_on_failed_query(monkeypatch, capsys):
    """An open-loop request whose search raises is counted as an error
    AND makes the serving CLI exit non-zero (it used to print the error
    column and return 0)."""
    from repro.data.corpus import DATASET_SPECS, DatasetSpec
    from repro.launch import serve

    monkeypatch.setitem(DATASET_SPECS, "tiny", DatasetSpec(
        "tiny", n_docs=24, n_queries=8, n_topics=4, doc_len_mean=20,
        doc_len_std=4))
    armed = {"on": False}
    warmup, search = ServingEngine.warmup, MultiVectorIndex.search_batch

    def warmup_then_arm(self):
        warmup(self)
        armed["on"] = True

    def fail_once(self, *a, **kw):
        if armed["on"]:
            armed["on"] = False
            raise RuntimeError("injected search failure")
        return search(self, *a, **kw)

    monkeypatch.setattr(ServingEngine, "warmup", warmup_then_arm)
    monkeypatch.setattr(MultiVectorIndex, "search_batch", fail_once)
    rc = serve.main(["--dataset", "tiny", "--backend", "flat",
                     "--queries", "6", "--arrival-qps", "200",
                     "--max-batch", "2"])
    assert rc == 1
    assert "open-loop request(s) errored" in capsys.readouterr().out
