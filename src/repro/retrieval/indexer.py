"""Indexer: encode -> TOKEN POOL -> index. The paper's pipeline, end to end.

``Indexer.build`` runs the document side:
  1. encode documents in device batches with the ColBERT encoder,
  2. apply ``pool_doc_embeddings`` (the paper's technique — method +
     pooling factor are config knobs; factor 1 = the unpooled baseline),
  3. hand the per-document (compacted) vector lists to the chosen index
     backend (flat | hnsw | plaid).

``Indexer.build_streaming`` is the same pipeline with bounded host
memory: an ITERATOR of token batches is encoded+pooled batch by batch,
and the pooled buffer is flushed into a new on-disk shard whenever
``shard_max_vectors`` is hit — peak host footprint is O(shard), not
O(corpus) (the prerequisite the pooled-footprint win needs to survive
corpora bigger than RAM). Flushed shards are immediately saved and
re-opened mmap'd, so the finished ``ShardedIndex`` holds file mappings,
not buffers.

Flushing is PIPELINED by default: a single background thread runs the
host-side shard construction + save + mmap-reopen while the build
thread encodes the next batches, double-buffered through a depth-1
queue, so the encode loop waits on shard I/O only when a flush backlog
fills the queue (``IndexStats.flush_wait_s`` is that wait;
``pipeline=False`` pins the serial path, which the bench's parity gate
builds against — shard order, doc ids and artifact bytes are identical
either way). The flush thread shares the process, and Python's
interpreter lock, with the encode loop, so its host work still delays
the loop's dispatches; and the loop hands the device one encode batch
at a time (see ``encode_and_pool_counted``), so the device idles while
the host fetches each batch's pooled rows.

Each stage runs under a host span (``repro.obs``): the wait for the
next token batch, encode dispatch, pooling dispatch, the fetch of
pooled rows, the wait to hand a shard over, and on the flush thread
the shard's index build, save and reopen.

Data-parallel posture: document batches are independent, so under pjit the
encode+pool step shards on the ``data`` axis; the index build consumes the
gathered host-side lists (index construction is host-bound bookkeeping).
"""
from __future__ import annotations

import dataclasses
import json
import os
import queue
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass
from typing import Iterable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

import jax

from repro import obs
from repro.configs.base import ColbertConfig
from repro.core.index import BACKENDS, MultiVectorIndex
from repro.core.pooling import (compact_pooled, compact_pooled_begin,
                                compact_pooled_finish)
from repro.core.spec import IndexSpec, PoolingSpec
from repro.kernels.ward_pool.ops import ward_block_b
from repro.models.colbert import encode_docs

# tiny jit'd reduction: the eager astype+sum pair costs ~2ms of op-by-op
# dispatch per batch on CPU, which serializes the encode stream
_emit_count = jax.jit(lambda emit: jnp.sum(emit.astype(jnp.int32)))
_END = object()     # end of a token-batch stream


class EncodedDocs:
    """A corpus encoded ONCE, reusable across many pooling configs.

    Holds the per-encode-batch ``(vectors, emit_mask, n_real_docs)``
    triples exactly as ``Indexer.encode_and_pool_counted`` would have
    produced them in-line (same batch boundaries, same padding), so
    pooling+indexing from an ``EncodedDocs`` is bitwise identical to
    re-encoding the raw tokens — minus the encoder forward passes.

    This is what lets the quality sweep (``repro.eval.sweep``) build a
    pool_factor x method x backend grid with ONE encoder pass over the
    corpus: pass an ``EncodedDocs`` anywhere ``Retriever.build`` or
    ``Indexer.build`` takes a ``[N, L]`` token array. (Streaming builds
    keep raw tokens — their point is never materializing the corpus.)
    """

    def __init__(self, batches, n_docs: int, encode_batch: int):
        self.batches = batches      # [(v [B,N,d], emit [B,N], n_real)]
        self.n_docs = int(n_docs)
        self.encode_batch = int(encode_batch)

    @classmethod
    def encode(cls, params, cfg: ColbertConfig, doc_tokens: np.ndarray,
               encode_batch: int = 64) -> "EncodedDocs":
        """Run the document encoder over ``doc_tokens`` [N, L] with the
        Indexer's exact batching (chunks of ``encode_batch``, last
        chunk zero-padded to full width) and keep the device outputs."""
        doc_tokens = np.asarray(doc_tokens)
        N, B = doc_tokens.shape[0], int(encode_batch)
        batches = []
        for lo in range(0, N, B):
            chunk = doc_tokens[lo:lo + B]
            pad = B - chunk.shape[0]
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            v, emit = encode_docs(params, jnp.asarray(chunk), cfg)
            batches.append((v, emit, B - pad))
        return cls(batches, n_docs=N, encode_batch=B)

    def nbytes(self) -> int:
        """Device bytes held by the cached encodes (sweep budgeting)."""
        return sum(int(v.size) * v.dtype.itemsize
                   + int(emit.size) for v, emit, _ in self.batches)


@dataclass
class IndexStats:
    n_docs: int
    n_vectors_raw: int
    n_vectors_stored: int
    index_bytes: int     # real serialized artifact size (core/persist.py)
    # device-resident bytes of the query-time doc representation (plaid:
    # packed views + codec; 0 for backends predating the field)
    device_bytes: int = 0
    # streaming/sharded builds only (defaults keep monolithic stats stable)
    n_shards: int = 1
    peak_buffered_vectors: int = 0   # host-buffer high-water mark
    max_batch_vectors: int = 0       # largest single encode-batch yield
    # pipelined-flush trace (streaming only; zeros for monolithic /
    # serial builds keep older stats.json consumers stable)
    pipelined: bool = False
    flush_wait_s: float = 0.0        # encode-side stall behind shard I/O
    flush_busy_s: float = 0.0        # wall spent inside flush (any thread)

    @property
    def vector_reduction(self) -> float:
        if self.n_vectors_raw == 0:
            return 0.0
        return 1.0 - self.n_vectors_stored / self.n_vectors_raw

    def to_json(self) -> dict:
        return dict(dataclasses.asdict(self),
                    vector_reduction=self.vector_reduction)


class Indexer:
    def __init__(self, params, cfg: ColbertConfig,
                 pool_method: Optional[str] = None,
                 pool_factor: Optional[int] = None,
                 backend: Optional[str] = None,
                 encode_batch: int = 64,
                 index_spec: Optional[IndexSpec] = None,
                 pooling_spec: Optional[PoolingSpec] = None,
                 **index_kw):
        """The typed surface is ``index_spec``/``pooling_spec``
        (core/spec.py) — what ``repro.Retriever`` passes. The loose
        ``pool_method``/``pool_factor``/``backend`` names remain as
        equivalent shorthand; raw ``**index_kw`` construction knobs are
        DEPRECATED in favour of ``index_spec=IndexSpec(...)``.
        """
        self.params = params
        self.cfg = cfg
        if index_spec is not None and (backend is not None or index_kw):
            raise TypeError("pass either index_spec or loose "
                            "backend/**index_kw knobs, not both")
        if pooling_spec is not None and (pool_method is not None
                                         or pool_factor is not None):
            raise TypeError("pass either pooling_spec or loose "
                            "pool_method/pool_factor knobs, not both")
        if index_kw:
            warnings.warn(
                "Indexer(**index_kw) is deprecated; pass "
                "index_spec=repro.IndexSpec(...) (see repro.core.spec)",
                DeprecationWarning, stacklevel=2)
        if index_spec is None:
            index_spec = IndexSpec.from_config(
                cfg, backend=backend or cfg.index_backend, **index_kw)
        if index_spec.backend not in BACKENDS:
            raise ValueError(
                f"Indexer builds {BACKENDS} indexes; backend "
                f"{index_spec.backend!r} builds through repro.Retriever")
        if pooling_spec is None:
            pooling_spec = PoolingSpec(
                method=pool_method or cfg.pool_method,
                factor=max(int(pool_factor if pool_factor is not None
                               else cfg.pool_factor), 1))
        self.index_spec = index_spec
        self.pooling = pooling_spec
        # legacy attribute surface (serve/bench reporting reads these)
        self.pool_method = pooling_spec.method
        self.pool_factor = pooling_spec.factor
        self.backend = index_spec.backend
        self.encode_batch = encode_batch
        self.batches_encoded = 0    # the next encode batch's number

    def _index_kw(self) -> dict:
        """Index construction knobs — ``IndexSpec.params()``, ONE
        definition for both build paths (monolithic and streaming must
        construct identical indexes)."""
        return self.index_spec.params()

    def encode_and_pool(self, doc_tokens) -> List[np.ndarray]:
        """doc_tokens [N, L] (or an :class:`EncodedDocs`) -> list of
        per-doc pooled vector arrays."""
        return self.encode_and_pool_counted(doc_tokens)[0]

    def _encoded_batches(self, doc_tokens):
        """Yield (batch number, vectors [B,N,d], emit [B,N], n_real_docs)
        per encode batch — from the encoder, or straight from an
        :class:`EncodedDocs` cache (same boundaries, same padding, so
        downstream pooling sees identical inputs either way)."""
        if isinstance(doc_tokens, EncodedDocs):
            for v, emit, n_real in doc_tokens.batches:
                yield self._next_batch(), v, emit, n_real
            return
        N, B = doc_tokens.shape[0], self.encode_batch
        for lo in range(0, N, B):
            chunk = doc_tokens[lo:lo + B]
            pad = B - chunk.shape[0]
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            b = self._next_batch()
            L = self.cfg.doc_maxlen
            with obs.span(obs.INDEXER_ENCODE, batch=b, docs=B - pad,
                          h2d_bytes=obs.host_nbytes(chunk), tokens=B * L,
                          valid_tokens=2 * B + int(np.count_nonzero(
                              chunk[:, :L - 2]))):
                v, emit = encode_docs(self.params, jnp.asarray(chunk),
                                      self.cfg)
            yield b, v, emit, B - pad

    def _next_batch(self) -> int:
        b = self.batches_encoded
        self.batches_encoded += 1
        return b

    def encode_and_pool_counted(
            self, doc_tokens
    ) -> Tuple[List[np.ndarray], int]:
        """(pooled per-doc arrays, raw emitted-vector count) from ONE
        encode pass — the emit mask each batch already computes is the
        unpooled count, so no second ``prepare_doc_tokens`` sweep over
        the corpus (the old ``_raw_vector_count``) is needed. An
        :class:`EncodedDocs` input skips the encoder entirely and pools
        the cached batches (bitwise-identical output).

        Within one call it runs a 1-deep software pipeline: batch i+1's
        encode+pool+compact is DISPATCHED before batch i's compacted
        rows are pulled to the host, so the host-side fetch/split
        overlaps the next batch's device compute (dispatch is async;
        only the fetch blocks). Raw counts stay device-resident scalars
        until the end for the same reason. Output order and bits are
        unaffected — batches are fetched strictly in order. A call that
        holds one encode batch (``build_streaming`` makes one call per
        batch) has nothing to overlap: its fetch and the raw-count sync
        wait for the batch's whole device work, and the device idles
        until the caller dispatches the next batch.
        """
        out: List[np.ndarray] = []
        raw_parts = []      # device scalars; materialized once at the end
        pending = None      # (batch number, compaction ticket | docs, n real)

        def fetch(prev) -> int:
            """Append a pending batch's docs to ``out``; returns the
            bytes fetched from the device."""
            _, ticket, keep = prev
            docs, moved = ((ticket, 0) if isinstance(ticket, list)
                           else compact_pooled_finish(ticket))
            out.extend(docs[:keep] if keep < len(docs) else docs)
            return moved

        ward = self.pooling.method == "ward"
        for b, v, emit, n_real in self._encoded_batches(doc_tokens):
            n_max = int(v.shape[1])
            with obs.span(obs.INDEXER_POOL, batch=b, n_max=n_max,
                          block_b=ward_block_b(n_max) if ward else 0):
                pooled, pmask = self.pooling.apply(v, emit)
                if n_real < emit.shape[0]:
                    # padding rows still emit their CLS/[D] markers —
                    # drop them from the raw count (and their docs below)
                    emit = emit[:n_real]
                raw_parts.append(_emit_count(emit))
                if isinstance(pooled, jnp.ndarray):
                    ticket = compact_pooled_begin(pooled, pmask)
                else:       # host-resident strategy output: no pipeline
                    ticket = compact_pooled(pooled, pmask)
            if pending is not None:
                with obs.span(obs.INDEXER_FETCH, batch=pending[0]) as sp:
                    sp.set_metadata(d2h_bytes=fetch(pending))
            pending = (b, ticket, n_real)
        if pending is None:
            return out, 0
        with obs.span(obs.INDEXER_FETCH, batch=pending[0]) as sp:
            moved = fetch(pending)
            raw = [np.asarray(r) for r in raw_parts]
            sp.set_metadata(d2h_bytes=moved + obs.host_nbytes(*raw))
        return out, int(np.sum(raw))

    def build(self, doc_tokens: np.ndarray,
              out_dir: Optional[str] = None):
        """Returns (MultiVectorIndex, IndexStats).

        ``out_dir`` writes the index artifact (core/persist.py) plus a
        ``stats.json`` beside its manifest, so the built index can be
        re-served by ``Searcher.from_dir`` / ``serve --index-dir``
        without re-encoding the corpus. ``index_bytes`` is always the
        *serialized* size — what the artifact occupies on disk — not
        the in-memory high-water mark.
        """
        from repro.core.persist import artifact_bytes, serialized_nbytes
        doc_vecs, raw = self.encode_and_pool_counted(doc_tokens)
        index = MultiVectorIndex(dim=self.cfg.proj_dim,
                                 backend=self.backend, **self._index_kw())
        index.add(doc_vecs)
        if out_dir is not None:
            manifest = index.save(out_dir, extra_meta={
                "pool": self.pooling.manifest_meta()})
            index_bytes = artifact_bytes(manifest)
        else:
            index_bytes = serialized_nbytes(index)
        stats = IndexStats(
            n_docs=index.n_docs,
            n_vectors_raw=raw,
            n_vectors_stored=index.n_vectors(),
            index_bytes=index_bytes,
            device_bytes=index.device_bytes(),
        )
        if out_dir is not None:
            with open(os.path.join(out_dir, "stats.json"), "w") as fh:
                json.dump(stats.to_json(), fh, indent=2)
        return index, stats

    # ------------------------------------------------------------- streaming
    def build_streaming(self, token_batches: Iterable[np.ndarray],
                        shard_max_vectors: int,
                        out_dir: Optional[str] = None,
                        probe_threads: int = 0,
                        pipeline: bool = True):
        """Bounded-memory build: token-batch stream -> capped shards.

        Args:
          token_batches: iterable of [n_b, L] doc-token arrays (a single
            [N, L] array is accepted and split into encode batches).
          shard_max_vectors: flush a shard once the pooled buffer holds
            at least this many vectors. Peak host memory is bounded by
            ``shard_max_vectors`` plus one encode batch's yield (docs
            are atomic; the flush check runs after each batch) — the
            realized bound is reported as
            ``IndexStats.peak_buffered_vectors``.
          probe_threads: stage-1 probe pool width for the built index
            (``ShardSpec.probe_threads``; 0 = auto). A pinned value is
            recorded in the root manifest and restored on load.
          out_dir: when given, every flushed shard is saved to
            ``out_dir/shard_XXXXX`` and REOPENED mmap'd — the buffer's
            bytes move to disk at flush, and the root manifest +
            aggregated ``stats.json`` are published at the end. Without
            it the shards stay host-resident (still capped per shard).
          pipeline: overlap shard construction/save/mmap-reopen with
            the device encode of the next batches on ONE background
            thread, double-buffered through a depth-1 handoff queue
            (at most one shard group queued + one in flight, so the
            transient host footprint adds <= 2 shard groups on top of
            the buffer bound). Groups are flushed strictly FIFO, so
            shard order, doc ids and artifact bytes are identical to
            ``pipeline=False`` — the bench gates that parity.
            ``peak_buffered_vectors`` accounting is unchanged.

        Returns (ShardedIndex, IndexStats) — stats aggregated across
        shards, ids global and contiguous in stream order.
        """
        from repro.core.persist import (_shard_dirname, artifact_bytes,
                                        finalize_sharded)
        from repro.core.sharded import ShardedIndex

        assert shard_max_vectors > 0, shard_max_vectors
        if isinstance(token_batches, EncodedDocs):
            raise TypeError(
                "build_streaming takes raw token batches — the point of "
                "the streaming path is never materializing the corpus; "
                "EncodedDocs caches feed monolithic builds only")
        if isinstance(token_batches, np.ndarray):
            arr, B = token_batches, self.encode_batch
            token_batches = (arr[lo:lo + B]
                             for lo in range(0, len(arr), B))
        sharded = ShardedIndex(dim=self.cfg.proj_dim, backend=self.backend,
                               shard_max_vectors=shard_max_vectors,
                               probe_threads=probe_threads,
                               **self._index_kw())

        buffer: "deque[np.ndarray]" = deque()
        buffered = 0
        raw = 0
        peak = 0
        max_batch = 0
        flush_wait_s = 0.0
        flush_busy_s = 0.0
        submitted = 0       # shard groups handed over so far

        def flush(docs_group: List[np.ndarray]) -> None:
            nonlocal flush_busy_s
            t0 = time.perf_counter()
            n = sharded.n_shards
            with obs.span(obs.INDEXER_SHARD, shard=n, docs=len(docs_group),
                          vectors=sum(len(d) for d in docs_group)):
                shard = sharded._new_shard()
                shard.add(docs_group)
                if out_dir is not None:
                    # bytes leave the host: save, drop, reopen mmap'd
                    sub = os.path.join(out_dir, _shard_dirname(n))
                    with obs.span(obs.INDEXER_SHARD_SAVE, shard=n):
                        shard.save(sub)
                    with obs.span(obs.INDEXER_SHARD_REOPEN, shard=n):
                        sharded.shards[-1] = MultiVectorIndex.load(
                            sub, mmap=True)
            flush_busy_s += time.perf_counter() - t0

        # -- single background flush lane (only this thread ever touches
        # sharded during the build, so shard numbering stays serial) --
        handoff: "queue.Queue" = queue.Queue(maxsize=1)
        failures: List[BaseException] = []

        def flush_worker() -> None:
            while True:
                group = handoff.get()
                if group is None:
                    return
                try:
                    if not failures:
                        flush(group)
                except BaseException as exc:  # surfaced by submit/join
                    failures.append(exc)

        worker = None
        if pipeline:
            worker = threading.Thread(target=flush_worker,
                                      name="indexer-flush", daemon=True)
            worker.start()

        def submit(docs_group: List[np.ndarray]) -> None:
            nonlocal flush_wait_s, submitted
            if failures:
                raise failures[0]
            shard, submitted = submitted, submitted + 1
            if worker is None:
                flush(docs_group)
                return
            with obs.span(obs.INDEXER_FLUSH_WAIT, shard=shard,
                          batch=self.batches_encoded - 1) as sp:
                t0 = time.perf_counter()
                handoff.put(docs_group)   # blocks only on a flush backlog
                waited = time.perf_counter() - t0
                sp.set_metadata(wait_us=int(waited * 1e6))
            flush_wait_s += waited

        batches = iter(token_batches)
        try:
            while True:
                with obs.span(obs.INDEXER_INPUT,
                              batch=self.batches_encoded):
                    batch = next(batches, _END)
                if batch is _END:
                    break
                batch = np.asarray(batch)
                if batch.size == 0:
                    continue
                docs, raw_b = self.encode_and_pool_counted(batch)
                raw += raw_b
                got = sum(len(d) for d in docs)
                max_batch = max(max_batch, got)
                buffer.extend(docs)
                buffered += got
                peak = max(peak, buffered)
                while buffered >= shard_max_vectors:
                    # pop one shard's worth off the head; docs are
                    # atomic, so the first doc always goes in and the
                    # shard never splits one (O(docs-taken) per flush —
                    # no tail copy of the remaining buffer)
                    group: List[np.ndarray] = []
                    used = 0
                    while buffer:
                        nxt = used + len(buffer[0])
                        if group and nxt > shard_max_vectors:
                            break
                        group.append(buffer.popleft())
                        used = nxt
                    submit(group)
                    buffered -= used
            if buffer:
                submit(list(buffer))
                buffer.clear()
        finally:
            if worker is not None:
                handoff.put(None)
                worker.join()
        if failures:
            raise failures[0]

        if out_dir is not None:
            manifest = finalize_sharded(sharded, out_dir, extra_meta={
                "pool": self.pooling.manifest_meta()})
            index_bytes = artifact_bytes(manifest)
        else:
            from repro.core.persist import serialized_nbytes
            index_bytes = sum(serialized_nbytes(s) for s in sharded.shards)
        stats = IndexStats(
            n_docs=sharded.n_docs,
            n_vectors_raw=raw,
            n_vectors_stored=sharded.n_vectors(),
            index_bytes=index_bytes,
            device_bytes=sharded.device_bytes(),
            n_shards=sharded.n_shards,
            peak_buffered_vectors=peak,
            max_batch_vectors=max_batch,
            pipelined=bool(pipeline),
            flush_wait_s=flush_wait_s,
            flush_busy_s=flush_busy_s,
        )
        if out_dir is not None:
            with open(os.path.join(out_dir, "stats.json"), "w") as fh:
                json.dump(stats.to_json(), fh, indent=2)
        return sharded, stats
