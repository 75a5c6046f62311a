"""ShardedIndex: K capped MultiVectorIndex shards behind ONE logical index.

The paper shrinks the *stored* index; this module makes the stored index
scale past what one host buffer (or one build pass) can hold, the way
ColBERTv2/PLAID chunk their index construction. A ``ShardedIndex`` owns
an ordered list of ``MultiVectorIndex`` shards plus the global doc-id
partition:

  * shard ``s`` owns the contiguous global id range
    ``[doc_base[s], doc_base[s] + shards[s].n_docs)`` — ids are assigned
    in stream order, so a sharded build numbers documents exactly like
    the monolithic build it replaces;
  * ``add`` routes to the LAST shard and spills into a fresh shard when
    ``shard_max_vectors`` would be exceeded (only the last shard ever
    grows, so earlier ranges stay frozen);
  * ``delete`` maps global ids -> owning shard via the doc_base table
    (one ``searchsorted``, no per-id loop);
  * ``search_batch`` fans the batched two-stage engine out per shard —
    each shard produces its exact-MaxSim scored slate
    (``MultiVectorIndex.scored_candidates``) and immediately reduces it
    to a DEVICE-RESIDENT local top-k with global ids
    (``maxsim.topk_shard``) — then the merge concatenates the [Nq, k]
    blocks in shard order and takes one global top-k. Full-width slates
    (up to corpus-wide for dense shards) never cross the host boundary;
    only k entries per shard move, device-to-device when shards are
    placed (``place``). Per-shard top-k is lossless for the global
    top-k (a shard contributes at most k winners) and ``lax.top_k``
    orders ties by lowest position, so the merged result — ids, scores,
    AND tie order — is bit-identical to concat-then-top-k and therefore
    to the monolithic index.

Parity contract (locked by tests/test_sharded*.py + test_replicated*):
with every backend's candidate stage exhaustive (flat always;
hnsw_candidates / plaid nprobe + ndocs generous) and — for plaid — one
codec shared across shards (``MultiVectorIndex.set_codec``; the
streaming builder trains it on the first shard), ``search_batch``
returns exactly the monolithic result: same ids, same scores, same tie
order.

Shard probing fans out on a thread pool (``probe_threads``; 0 = auto =
``min(8, cpu_count)``, pinnable via ``ShardSpec.probe_threads``):
stage 1 is host-bound numpy for hnsw/plaid, so K shards probe
concurrently while the merge stays deterministic — per-shard top-k
blocks are collected back in shard order, so results are identical to
the sequential fan-out. When shards are ``place``d on devices
(core/replicated.py), each shard's stage-2 executables and lazy device
caches commit to its own device, so the fan-out is device-parallel,
not just thread-parallel. Per-shard probe times are returned per call
by ``search_batch_with_stats`` (concurrent batches each get their own
timings); ``last_probe_s`` keeps the last call's timings as a
convenience snapshot, written in one atomic assignment so a concurrent
reader never sees a half-built list.
"""
from __future__ import annotations

import contextlib
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import BACKENDS, PARAM_KEYS, MultiVectorIndex
from repro.core.maxsim import topk_dispatch, topk_shard

# shard construction knobs forwarded verbatim to MultiVectorIndex — the
# same set the persistence manifest records (one definition for all
# three, owned by the spec layer: core/spec.py INDEX_PARAM_KEYS)
SHARD_PARAM_KEYS = PARAM_KEYS


def _resolve_probe_threads(probe_threads: int) -> int:
    """0 = auto (the historical ``min(8, cpu_count)`` default)."""
    pt = int(probe_threads)
    if pt < 0:
        raise ValueError(f"probe_threads must be >= 0 (0 = auto), "
                         f"got {probe_threads!r}")
    return pt if pt > 0 else min(8, os.cpu_count() or 1)


class ShardedIndex:
    """One logical multi-vector index over capped on-disk/in-memory shards."""

    def __init__(self, dim: int, backend: str = "plaid",
                 shard_max_vectors: int = 0, probe_threads: int = 0,
                 **index_kw):
        assert backend in BACKENDS, backend
        unknown = set(index_kw) - set(SHARD_PARAM_KEYS)
        assert not unknown, f"unknown shard params {sorted(unknown)}"
        self.dim = dim
        self.backend = backend
        self.shard_max_vectors = int(shard_max_vectors)  # 0 = uncapped
        self.index_kw: Dict = dict(index_kw)
        self.shards: List[MultiVectorIndex] = []
        self.doc_base: List[int] = []
        self.last_probe_s: List[float] = []
        # the SPEC value (0 = auto) persists through manifests; the
        # resolved worker count drives the pool
        self.probe_threads = _resolve_probe_threads(probe_threads)
        self.probe_threads_cfg = int(probe_threads)
        # per-shard jax devices (core/replicated.py ``place``); None =
        # default device for everything
        self.shard_devices: Optional[List] = None
        self._closed = False
        # created eagerly (no threads spawn until first submit) so
        # concurrent first searches can't race a lazy init; close()
        # releases the workers when the index is retired
        self._pool = ThreadPoolExecutor(
            max_workers=max(self.probe_threads, 1),
            thread_name_prefix="shard-probe")

    @classmethod
    def from_parts(cls, shards: Sequence[MultiVectorIndex],
                   doc_base: Sequence[int],
                   shard_max_vectors: int = 0,
                   probe_threads: int = 0) -> "ShardedIndex":
        """Adopt already-built shards (persistence / streaming build).

        ``doc_base`` must be the cumulative doc counts: base[0] == 0 and
        base[s+1] == base[s] + shards[s].n_docs.
        """
        assert len(shards) == len(doc_base)
        first = shards[0] if len(shards) else None
        kw = ({k: getattr(first, k) for k in SHARD_PARAM_KEYS}
              if first is not None else {})
        self = cls(dim=(first.dim if first is not None else 0),
                   backend=(first.backend if first is not None else "flat"),
                   shard_max_vectors=shard_max_vectors,
                   probe_threads=probe_threads, **kw)
        base = 0
        for s, b in zip(shards, doc_base):
            assert s.backend == self.backend and s.dim == self.dim
            assert int(b) == base, (b, base)
            base += s.n_docs
        self.shards = list(shards)
        self.doc_base = [int(b) for b in doc_base]
        return self

    # ------------------------------------------------------------- topology
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def n_docs(self) -> int:
        if not self.shards:
            return 0
        return self.doc_base[-1] + self.shards[-1].n_docs

    def n_vectors(self) -> int:
        return sum(s.n_vectors() for s in self.shards)

    def nbytes(self) -> int:
        return sum(s.nbytes() for s in self.shards)

    def device_bytes(self) -> int:
        """Device-resident bytes of the query-time doc representation,
        summed over shards (see ``MultiVectorIndex.device_bytes``)."""
        return sum(s.device_bytes() for s in self.shards)

    def shard_of(self, doc_ids: np.ndarray) -> np.ndarray:
        """Global doc ids -> owning shard index (vectorized). An empty
        id array is a well-typed no-op — an empty int array back — even
        on an empty index (the CRUD paths route nothing)."""
        ids = np.asarray(doc_ids, np.int64)
        if ids.size == 0:
            return np.zeros(ids.shape, np.int64)
        if not self.shards:
            raise IndexError("empty sharded index")
        if ids.min() < 0 or ids.max() >= self.n_docs:
            raise IndexError(f"doc id out of range [0, {self.n_docs})")
        return np.searchsorted(np.asarray(self.doc_base, np.int64), ids,
                               side="right") - 1

    def codec(self):
        """The shared plaid residual codec (None for other backends)."""
        for s in self.shards:
            if s._plaid is not None:
                return s._plaid.codec
        return None

    # ------------------------------------------------------------- placement
    def place(self, devices: Optional[Sequence]) -> None:
        """Pin shard ``s``'s stage-2 compute — and the device caches it
        builds lazily (padded stores, packed code views) — to
        ``devices[s]``. ``None`` clears placement (default device).
        The next ``warm_shapes`` traces per placed device; results are
        bitwise identical wherever shards land (the merge re-collects
        in shard order)."""
        if devices is None:
            self.shard_devices = None
            return
        devices = list(devices)
        assert len(devices) == len(self.shards), \
            (len(devices), len(self.shards))
        self.shard_devices = devices

    def _shard_device(self, i: int):
        return self.shard_devices[i] if self.shard_devices else None

    def set_probe_threads(self, probe_threads: int) -> None:
        """Re-pin the probe fan-out width after construction — e.g. the
        serving router divides the auto default across replica lanes so
        ``lanes x probe_threads`` never oversubscribes the host. Swaps
        in a fresh pool; in-flight probes finish on the old one."""
        new = _resolve_probe_threads(probe_threads)
        old = self._pool
        self._pool = ThreadPoolExecutor(
            max_workers=max(new, 1), thread_name_prefix="shard-probe")
        self.probe_threads = new
        self.probe_threads_cfg = int(probe_threads)
        old.shutdown(wait=False)

    def set_probe_kernel(self, probe_kernel: str) -> None:
        """Fan the RUNTIME-ONLY plaid candidate-path toggle to every
        shard (same non-persisted contract as ``packed_rerank``). Each
        shard's ``device_probe_plan`` still decides independently — a
        shard whose geometry fails the bitwise-safety proof keeps the
        host path."""
        from repro.core.plaid import PROBE_KERNELS
        assert probe_kernel in PROBE_KERNELS, probe_kernel
        for shard in self.shards:
            shard.probe_kernel = probe_kernel

    # ----------------------------------------------------------------- build
    def _new_shard(self) -> MultiVectorIndex:
        shard = MultiVectorIndex(dim=self.dim, backend=self.backend,
                                 **self.index_kw)
        if self.backend == "plaid":
            codec = self.codec()
            if codec is not None:       # ONE quantization model per index
                shard.set_codec(codec)
        self.doc_base.append(self.n_docs)
        self.shards.append(shard)
        if self.shard_devices is not None:      # growth drops placement
            self.shard_devices = None
        return shard

    def add(self, doc_vectors: List[np.ndarray]) -> np.ndarray:
        """Append docs; spills into new shards at ``shard_max_vectors``.

        Returns GLOBAL doc ids — contiguous, in input order, regardless
        of how the docs land on shards. An empty input is a no-op
        returning an empty id array.
        """
        doc_vectors = [np.asarray(v, np.float32).reshape(-1, self.dim)
                       for v in doc_vectors]
        out: List[np.ndarray] = []
        lens = [len(v) for v in doc_vectors]
        i = 0
        while i < len(doc_vectors):
            shard = self.shards[-1] if self.shards else self._new_shard()
            cap = self.shard_max_vectors
            if cap:
                room = cap - shard.n_vectors()
                j = i
                used = 0
                # docs are atomic: take at least one into an empty shard
                while j < len(doc_vectors) and (
                        used + lens[j] <= room or (j == i and
                                                   shard.n_docs == 0)):
                    used += lens[j]
                    j += 1
                if j == i:              # shard full: spill to a fresh one
                    self._new_shard()
                    continue
            else:
                j = len(doc_vectors)
            base = self.doc_base[-1]
            out.append(base + shard.add(doc_vectors[i:j]))
            i = j
        return (np.concatenate(out) if out else np.zeros((0,), np.int64))

    def delete(self, doc_ids) -> None:
        ids = np.asarray(doc_ids, np.int64).reshape(-1)
        if ids.size == 0:
            return
        owner = self.shard_of(ids)
        for s in np.unique(owner):
            local = ids[owner == s] - self.doc_base[s]
            self.shards[s].delete(local)

    # ------------------------------------------------------------ persistence
    def save(self, path: str, extra_meta: Optional[dict] = None) -> dict:
        """Root manifest + one artifact dir per shard (core/persist.py)."""
        from repro.core import persist
        return persist.save_sharded(self, path, extra_meta=extra_meta)

    @classmethod
    def load(cls, path: str, mmap: bool = True) -> "ShardedIndex":
        from repro.core import persist
        return persist.load_sharded(path, mmap=mmap)

    # ----------------------------------------------------------------- search
    def warm_shapes(self, qs: np.ndarray, k: int = 10) -> None:
        """Pre-compile the candidate-width ladder on every shard plus
        the per-shard device top-k (``topk_shard``) at every reachable
        slate width AND the merged top-k for this batch shape (serving
        warmup). Runs under each shard's placed device, so per-device
        executable caches fill before traffic."""
        qs = np.asarray(qs, np.float32)
        Nq = len(qs)
        for i, (base, shard) in enumerate(zip(self.doc_base, self.shards)):
            dev = self._shard_device(i)
            ctx = (jax.default_device(dev) if dev is not None
                   else contextlib.nullcontext())
            with ctx:
                shard.warm_shapes(qs, k=k)
                if shard.n_docs == 0:
                    continue
                widths, dense = shard.candidate_widths(qs)
                for C in widths:
                    topk_shard(jnp.full((Nq, C), -jnp.inf, jnp.float32),
                               np.zeros((Nq, C), np.int64), k, base)
                if dense:
                    topk_shard(jnp.full((Nq, shard.n_docs), -jnp.inf,
                                        jnp.float32), None, k, base)
        self.search_batch(qs, k=k)

    def _probe_shard(self, base: int, shard: MultiVectorIndex,
                     qs: np.ndarray, q_mask, k: int, dev=None):
        """One shard's device-resident local top-k with GLOBAL ids, plus
        its probe wall time — the unit the thread pool fans out. Under
        ``dev`` (when placed) every device array this touches — the
        shard's lazy caches included — commits to that device."""
        t0 = time.perf_counter()
        ctx = (jax.default_device(dev) if dev is not None
               else contextlib.nullcontext())
        with ctx:
            scores, cand = shard.scored_candidates(qs, q_mask)
            top_s, top_i = topk_shard(scores, cand, k, base)
        dt = time.perf_counter() - t0
        return top_s, top_i, dt

    def close(self) -> None:
        """Release the probe thread pool (idempotent). Called when a
        serving runtime retires a hot-swapped-out generation — without
        it, every swapped-in sharded index would leak its workers for
        the life of the process. A closed index still serves (the
        fan-out degrades to sequential probing)."""
        self._closed = True
        self._pool.shutdown(wait=False)

    @property
    def closed(self) -> bool:
        return self._closed

    def search_batch_with_stats(
            self, qs: np.ndarray, k: int = 10,
            q_mask: Optional[np.ndarray] = None
    ) -> Tuple[np.ndarray, np.ndarray, List[float]]:
        """``search_batch`` plus this call's per-shard probe seconds.

        Fan-out: each live shard runs candidates + exact rerank and
        reduces its scored slate to a device-local top-k with global
        ids — on the shard thread pool when more than one live shard
        and ``probe_threads > 1`` (stage 1 is host-bound numpy for
        hnsw/plaid, so shards probe concurrently), each under its
        placed device when ``place``d. Merge: the [Nq, k] blocks are
        collected IN SHARD ORDER, moved device-to-device onto the first
        live shard's device (a no-op when unplaced), concatenated along
        the candidate axis, and one shared top-k picks the global
        winners — thread scheduling can never reorder the merge, and
        per-shard top-k loses no candidate a global top-k could keep,
        so results match the sequential concat-everything fan-out
        exactly. Probe times are per-call state: concurrent batches
        each get their own list (the thread-safety contract
        ``last_probe_s`` alone could not provide).
        """
        out, probe_s = self._search_dispatch(qs, k, q_mask)
        S, I = out
        return S, I, probe_s

    def _search_dispatch(self, qs: np.ndarray, k: int,
                         q_mask: Optional[np.ndarray]):
        """The fan-out and the device merge, not fetched: (the result as
        ``search_batch(fetch=False)`` returns it, per-shard probe
        seconds)."""
        qs = np.asarray(qs, np.float32)
        Nq = len(qs)
        live = [(base, shard, self._shard_device(i))
                for i, (base, shard) in enumerate(
                    zip(self.doc_base, self.shards)) if shard.n_docs > 0]
        if len(live) > 1 and self.probe_threads > 1 and not self._closed:
            futs = [self._pool.submit(self._probe_shard, base, shard,
                                      qs, q_mask, k, dev)
                    for base, shard, dev in live]
            parts = [f.result() for f in futs]
        else:
            parts = [self._probe_shard(base, shard, qs, q_mask, k, dev)
                     for base, shard, dev in live]
        probe_s = []
        it = iter(parts)
        for shard in self.shards:
            probe_s.append(0.0 if shard.n_docs == 0 else next(it)[2])
        if not parts:
            return (np.full((Nq, k), -np.inf, np.float32),
                    np.full((Nq, k), -1, np.int64)), probe_s
        if len(parts) == 1:
            top_s, top_i = parts[0][0], parts[0][1]
        else:
            md = live[0][2]             # merge device (None = default)
            ss = [p[0] if md is None else jax.device_put(p[0], md)
                  for p in parts]
            ii = [p[1] if md is None else jax.device_put(p[1], md)
                  for p in parts]
            top_s = jnp.concatenate(ss, axis=1)
            top_i = jnp.concatenate(ii, axis=1)
        return topk_dispatch(top_s, top_i, k), probe_s

    def search_batch(self, qs: np.ndarray, k: int = 10,
                     q_mask: Optional[np.ndarray] = None, *,
                     fetch: bool = True):
        """qs [Nq, Lq, dim] -> (scores [Nq, k], ids [Nq, k]; -inf/-1 pads).

        See ``search_batch_with_stats`` for the fan-out/merge contract;
        this drops the probe stats, keeping only the ``last_probe_s``
        snapshot (one atomic assignment — safe to read, but concurrent
        callers needing *their* timings should use the stats variant).
        ``fetch=False`` returns the merged result as dispatched, as
        ``MultiVectorIndex.search_batch`` has it.
        """
        out, self.last_probe_s = self._search_dispatch(qs, k, q_mask)
        return tuple(out) if fetch else out

    def search(self, q: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        """q: [Lq, dim] -> (scores [k'], doc ids [k'])."""
        S, I = self.search_batch(np.asarray(q, np.float32)[None], k=k)
        valid = I[0] >= 0
        return S[0][valid], I[0][valid]
