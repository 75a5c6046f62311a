"""Dispatch-ahead in the serving engine's search lane, and the split
search it rests on.

* Every index type's ``search_batch(..., fetch=False)`` (and a replica
  lane's ``search_batch_on``) unpacks, once fetched, to what
  ``search_batch`` returns, bit for bit, -inf/-1 pads included.
* Under a backlog the lane dispatches batch i+1 before it fetches batch
  i; requests that arrive one batch at a time never engage it. Results
  stay bitwise those of a direct ``search_batch`` over the same rows:
  every request here fills one batch, so the engine and the direct call
  run the same shapes.
* Dispatching ahead holds no more batches than the pipeline depth
  allows, a drain resolves the batch held ahead, and a hot swap leaves
  a batch dispatched ahead on the generation it was dispatched on.
"""
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from repro import obs
from repro.core.index import MultiVectorIndex
from repro.core.maxsim import PendingTopK
from repro.core.replicated import ReplicatedIndex
from repro.core.sharded import ShardedIndex
from repro.launch import engine as engine_mod
from repro.launch.engine import ServingEngine

from test_serving_engine import (DIM, LQ, VecSearcher, make_index,
                                 unit_docs, unit_queries)

B = 4                       # max_batch; every request here is one batch
K = 6


class CountingIndex:
    """Wraps an index and logs each dispatch and each fetch, in order,
    as ("dispatch", first row) / ("fetch", first row). ``fetch_s``
    slows every fetch, so a backlog builds behind the lane;
    ``before_dispatch`` and ``on_fetch`` are test hooks."""

    def __init__(self, inner, fetch_s=0.0):
        self.inner = inner
        self.fetch_s = fetch_s
        self.log = []
        self.fetch_flags = []
        self.before_dispatch = None
        self.on_fetch = None
        self._lock = threading.Lock()

    def search_batch(self, qs, k=10, *, fetch=True):
        if self.before_dispatch is not None:
            self.before_dispatch(len(self.dispatches()))
        tag = float(np.asarray(qs)[0, 0, 0])
        out = self.inner.search_batch(qs, k=k, fetch=False)
        with self._lock:
            self.log.append(("dispatch", tag))
            self.fetch_flags.append(fetch)
        pending = _Counted(self, out, tag)
        return tuple(pending) if fetch else pending

    def reset(self):
        """Forget the engine's warm-up searches."""
        self.log.clear()
        self.fetch_flags.clear()

    def dispatches(self):
        return [t for e, t in self.log if e == "dispatch"]

    def fetches(self):
        return [t for e, t in self.log if e == "fetch"]


class _Counted:
    def __init__(self, owner, out, tag):
        self.owner, self.out, self.tag = owner, out, tag

    def __iter__(self):
        o = self.owner
        time.sleep(o.fetch_s)
        if o.on_fetch is not None:
            o.on_fetch(self.tag)
        S, I = self.out
        with o._lock:
            o.log.append(("fetch", self.tag))
        return iter((S, I))


def batches(rng, n):
    """``n`` requests of B queries each; row 0 of request i starts with
    the value 10 + i, so a logged first row names its request."""
    qs = unit_queries(rng, n * B)
    qs[::B, 0, 0] = 10.0 + np.arange(n)
    return [qs[i * B:(i + 1) * B] for i in range(n)]


@contextmanager
def engine(index, **kw):
    eng = ServingEngine(VecSearcher(index), max_batch=B, max_wait_ms=1.0,
                        k=K, pipeline_depth=2, **kw)
    eng.start()
    index.reset()
    try:
        yield eng
    finally:
        eng.stop()


def times_ahead(log):
    """Dispatches made while an earlier dispatch was not yet fetched."""
    held, n = 0, 0
    for event, _ in log:
        if event == "dispatch":
            n += held > 0
            held += 1
        else:
            held -= 1
    return n


# A PLAID index whose candidates come from the device path: 16
# candidates a query at most (two shards: 32), so k = 40 runs past
# every slate.
PLAID_DEVICE = dict(backend="plaid", doc_maxlen=24, n_centroids=16,
                    ndocs=16, nprobe=2)


def device_plaid(sharded=False, n_docs=60, seed=0):
    docs = unit_docs(np.random.default_rng(seed), n=n_docs)
    cap = sum(len(d) for d in docs) // 2 + 20       # two shards
    idx = (ShardedIndex(dim=DIM, shard_max_vectors=cap, **PLAID_DEVICE)
           if sharded
           else MultiVectorIndex(dim=DIM, **PLAID_DEVICE))
    idx.add(docs)
    return idx


# ------------------------------------------------------ split search parity
def _index(backend, layout):
    if backend == "plaid_device":
        idx = device_plaid(sharded=layout != "monolithic", seed=3)
        if layout == "monolithic":
            assert idx._probe_plan(LQ)[0]
    else:
        idx = make_index(backend, sharded=layout != "monolithic",
                         n_docs=12, seed=3)
    return ReplicatedIndex.replicate(idx, 2) if layout == "replicated" \
        else idx


@pytest.mark.parametrize("layout", ["monolithic", "sharded", "replicated"])
@pytest.mark.parametrize("backend", ["flat", "hnsw", "plaid",
                                     "plaid_device"])
@pytest.mark.parametrize("k", [5, 40], ids=["k_in_slate", "k_past_slate"])
def test_dispatch_then_fetch_equals_search_batch(backend, layout, k):
    idx = _index(backend, layout)
    qs = unit_queries(np.random.default_rng(4), 5)
    S0, I0 = idx.search_batch(qs, k=k)
    lanes = [lambda **kw: idx.search_batch(qs, k=k, **kw)]
    if layout == "replicated":
        lanes += [lambda r=r, **kw: idx.search_batch_on(r, qs, k=k, **kw)
                  for r in range(2)]
    for search in lanes:
        out = search(fetch=False)
        # every backend's slate is scored on the device: still in flight
        assert isinstance(out, PendingTopK)
        S, I = out
        assert S.dtype == np.float32 and I.dtype == np.int64
        assert np.array_equal(S, S0) and np.array_equal(I, I0)
        assert out.fetch() is out.fetch()       # one copy out, kept
    if k == 40:                                 # past every slate
        assert np.isneginf(S0[:, 32:]).all() and (I0[:, 32:] == -1).all()
    assert ((I0 >= 0) == np.isfinite(S0)).all()


def test_empty_index_dispatch_is_host_resolved():
    idx = MultiVectorIndex(dim=DIM, backend="flat")
    S, I = idx.search_batch(np.zeros((2, LQ, DIM), np.float32), k=3,
                            fetch=False)
    assert np.isneginf(S).all() and (I == -1).all()


# ------------------------------------------------------------ engine parity
@pytest.mark.parametrize("backend", ["plaid", "flat", "hnsw"])
def test_engine_equals_direct_search_under_backlog(backend):
    if backend == "plaid":
        idx = device_plaid()
        assert idx._probe_plan(LQ)[0], "plaid must take its device path"
    else:
        idx = make_index(backend)
    reqs = batches(np.random.default_rng(1), 10)
    ref = [idx.search_batch(q, k=K) for q in reqs]
    counted = CountingIndex(idx, fetch_s=0.02)
    with engine(counted) as eng:
        futs = [eng.submit(q) for q in reqs]
        got = [f.result(timeout=60) for f in futs]
        snap = eng.stats.snapshot()
    for (S, I), (S0, I0) in zip(got, ref):
        assert np.array_equal(S, S0) and np.array_equal(I, I0)
    assert snap["failed"] == 0 and snap["batches"] == len(reqs)
    assert snap["dispatched_ahead"] == times_ahead(counted.log) >= 1


# ------------------------------------------------------------- engagement
def _recorded_search_spans(monkeypatch):
    seen = []
    real = obs.span

    def span(name, **args):
        if name == obs.ENGINE_SEARCH:
            seen.append(args)
        return real(name, **args)
    monkeypatch.setattr(engine_mod.obs, "span", span)
    return seen


def test_dispatch_ahead_engages_under_a_backlog(monkeypatch):
    """Ten batches submitted at once behind a slow fetch: every batch
    but the first one or two is dispatched before the one ahead of it
    is fetched, and the counter, the span arg and the index's own log
    of dispatches and fetches agree."""
    seen = _recorded_search_spans(monkeypatch)
    counted = CountingIndex(make_index("flat"), fetch_s=0.03)
    reqs = batches(np.random.default_rng(2), 10)
    with engine(counted) as eng:
        warm = len(seen)
        for f in [eng.submit(q) for q in reqs]:
            f.result(timeout=60)
        snap = eng.stats.snapshot()
    ahead = [a["ahead"] for a in seen[warm:]]
    n = len(reqs)
    assert len(ahead) == n and ahead[0] == 0
    assert n - 2 <= sum(ahead) == snap["dispatched_ahead"] <= n - 1
    assert times_ahead(counted.log) == sum(ahead)
    # each batch is dispatched once, unfetched, and fetched once
    assert sorted(counted.dispatches()) == sorted(counted.fetches())
    assert len(counted.dispatches()) == n
    assert not any(counted.fetch_flags)
    # results still come back in batch order
    assert counted.fetches() == [10.0 + i for i in range(n)]


def test_dispatch_ahead_stays_off_one_batch_at_a_time(monkeypatch):
    """A client that waits for each answer before it sends the next
    leaves nothing staged behind a batch: the lane fetches each batch
    at once."""
    seen = _recorded_search_spans(monkeypatch)
    counted = CountingIndex(make_index("flat"), fetch_s=0.01)
    with engine(counted) as eng:
        warm = len(seen)
        for q in batches(np.random.default_rng(3), 6):
            eng.submit(q).result(timeout=60)
        snap = eng.stats.snapshot()
    assert [a["ahead"] for a in seen[warm:]] == [0] * 6
    assert snap["dispatched_ahead"] == 0 == times_ahead(counted.log)
    assert snap["served"] == 6 * B


# --------------------------------------------------------------- lifecycle
def test_pending_batches_stay_within_the_pipeline_depth():
    """Depth 2 with one lane: at most 3 batches popped and unresolved
    (two held by the lane, one staged), and the backlog reaches that."""
    counted = CountingIndex(make_index("flat"), fetch_s=0.03)
    reqs = batches(np.random.default_rng(5), 10)
    peak = []
    with engine(counted) as eng:
        pop = eng._pop_coalesced

        def pop_and_count():
            out = pop()
            peak.append(eng._pending)
            return out
        eng._pop_coalesced = pop_and_count
        for f in [eng.submit(q) for q in reqs]:
            f.result(timeout=60)
        ahead = eng.stats.snapshot()["dispatched_ahead"]
    assert ahead >= len(reqs) - 2
    assert max(peak) == 3


def test_drain_resolves_the_batch_held_ahead():
    counted = CountingIndex(make_index("flat"), fetch_s=0.03)
    reqs = batches(np.random.default_rng(6), 6)
    eng = ServingEngine(VecSearcher(counted), max_batch=B, max_wait_ms=1.0,
                        k=K, pipeline_depth=2).start()
    counted.reset()
    futs = [eng.submit(q) for q in reqs]
    eng.stop(drain=True)
    assert all(f.done() for f in futs)
    for f, q in zip(futs, reqs):
        S, I = f.result(timeout=0)
        S0, I0 = counted.inner.search_batch(q, k=K)
        assert np.array_equal(S, S0) and np.array_equal(I, I0)
    snap = eng.stats.snapshot()
    assert snap["failed"] == 0 and snap["served"] == len(reqs) * B
    assert snap["dispatched_ahead"] >= 1
    assert not any(t.is_alive() for t in eng._threads)


def test_hot_swap_keeps_a_batch_held_ahead_on_its_generation():
    """Batch 1 is dispatched ahead on generation 0, and the swap lands
    before batch 0 is fetched. Batch 1 still answers from generation
    0's index, whose handle drains only once batch 1 is fetched; the
    batches dispatched after the swap answer from generation 1's."""
    old_ix = CountingIndex(make_index("flat", seed=11))
    new_ix = make_index("flat", seed=12)            # other docs
    reqs = batches(np.random.default_rng(7), 4)
    swapped = threading.Event()
    retiring, drained_at_fetch = [], []

    with engine(old_ix) as eng:
        def before_dispatch(n_before):
            # hold batch 0 until batch 1 is staged behind it
            deadline = time.time() + 10
            while n_before == 0 and not eng._staged \
                    and time.time() < deadline:
                time.sleep(1e-3)

        def on_fetch(tag):
            if tag == 10.0:         # batch 0: wait for the swap
                swapped.wait(timeout=10)
            if tag == 11.0:
                drained_at_fetch.append(retiring[0].wait_drained(0))

        old_ix.before_dispatch = before_dispatch
        old_ix.on_fetch = on_fetch
        futs = [eng.submit(q) for q in reqs]
        deadline = time.time() + 10
        while len(old_ix.dispatches()) < 2 and time.time() < deadline:
            time.sleep(1e-3)
        retiring.append(eng.swap_index(new_ix))
        swapped.set()
        got = [f.result(timeout=30) for f in futs]
        assert retiring[0].wait_drained(timeout=10)
        snap = eng.stats.snapshot()
    assert old_ix.dispatches() == [10.0, 11.0]
    assert times_ahead(old_ix.log) == 1
    assert drained_at_fetch == [False]
    for i, ((S, I), q) in enumerate(zip(got, reqs)):
        owner = old_ix.inner if i < 2 else new_ix
        S0, I0 = owner.search_batch(q, k=K)
        assert np.array_equal(S, S0) and np.array_equal(I, I0), i
    assert snap["generations_seen"] == [0, 0, 1, 1]
    assert snap["failed"] == 0
