"""``repro.Retriever`` — the spec-driven facade over the whole pipeline.

The paper's pitch is "a simple drop-in during indexation with any
ColBERT-like model". This module makes the drop-in ONE object driven by
ONE typed spec (core/spec.py): build -> persist -> serve without
touching the five layers underneath::

    import repro

    spec = repro.RetrieverSpec(
        pooling=repro.PoolingSpec(method="ward", factor=2),
        index=repro.IndexSpec.from_config(cfg, backend="plaid"))
    r = repro.Retriever.build(params, cfg, doc_tokens, spec,
                              out_dir="idx")       # encode+pool+index+save
    scores, ids = r.search(query_tokens, k=10)

    r2 = repro.Retriever.load(params, cfg, "idx")  # fresh process, mmap
    assert r2.spec.index == spec.index             # spec round-trips
    with r2.serve() as engine:                     # concurrent runtime
        fut = engine.submit(query_tokens[0])

Every backend in the registry — flat, hnsw, plaid, AND the
beyond-paper cascade — builds through the same entry point and serves
through the same batched engine; results are bitwise equal to the
pre-facade ``Indexer``/``Searcher``/``ServingEngine`` call paths
(tests/test_api.py pins all of it), which remain available underneath.

A new backend is a ``register_backend(name, kind, keys, builder)`` call:
the builder receives ``(params, cfg, docs, spec, out_dir)`` and returns
``(index, IndexStats)``; persistence dispatch rides the manifest
``kind`` it writes.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Iterable, List, Optional, Tuple, Union

import numpy as np

from repro.core.spec import (CASCADE_PARAM_KEYS, INDEX_PARAM_KEYS,
                             IndexSpec, PoolingSpec, RetrieverSpec,
                             ServeSpec, backend_info, register_backend,
                             retriever_spec_from_manifest)
from repro.retrieval.indexer import Indexer, IndexStats
from repro.retrieval.searcher import Searcher


def _as_token_array(docs):
    """Monolithic builds take one [N, L] token array; accept an
    iterator of batches too (the streaming input shape) and pass an
    :class:`EncodedDocs` cache (corpus encoded once, pooled many ways —
    the quality sweep's input) straight through."""
    from repro.retrieval.indexer import EncodedDocs
    if isinstance(docs, (np.ndarray, EncodedDocs)):
        return docs
    return np.concatenate([np.asarray(b) for b in docs])


def _write_stats(out_dir: str, stats: IndexStats) -> None:
    with open(os.path.join(out_dir, "stats.json"), "w") as fh:
        json.dump(stats.to_json(), fh, indent=2)


def _spec_extra_meta(spec: RetrieverSpec) -> dict:
    """The spec-carrying manifest entries for a save through the facade
    — derived from the SAME helpers ``manifest_meta_for`` uses, so the
    round-trip contract has one definition (core/spec.py)."""
    extra = {"pool": spec.pooling.manifest_meta()}
    if spec.index.backend == "cascade":
        # generic knobs don't drive the cascade build, but the full
        # spec must round-trip through the manifest
        extra["params"] = spec.index.generic_params()
    return extra


# ---------------------------------------------------------------------------
# Registry builders
# ---------------------------------------------------------------------------
def _build_multi_vector(params, cfg, docs, spec: RetrieverSpec,
                        out_dir: Optional[str], encode_batch: int):
    """flat | hnsw | plaid, monolithic or streaming-sharded."""
    indexer = Indexer(params, cfg, index_spec=spec.index,
                      pooling_spec=spec.pooling, encode_batch=encode_batch)
    if spec.shard.sharded:
        return indexer.build_streaming(
            docs, shard_max_vectors=int(spec.shard.shard_max_vectors),
            out_dir=out_dir,
            probe_threads=int(spec.shard.probe_threads))
    return indexer.build(_as_token_array(docs), out_dir=out_dir)


def _build_cascade(params, cfg, docs, spec: RetrieverSpec,
                   out_dir: Optional[str], encode_batch: int):
    """Encode once, pool twice (coarse + fine), store both levels."""
    from repro.core import persist
    from repro.retrieval.cascade import CascadeIndex

    docs = _as_token_array(docs)
    ix = spec.index
    flat = IndexSpec.from_config(cfg, backend="flat",
                                 doc_maxlen=ix.doc_maxlen)

    def pool(factor: int):
        return Indexer(params, cfg, index_spec=flat,
                       pooling_spec=spec.pooling.replace(
                           factor=max(int(factor), 1)),
                       encode_batch=encode_batch)

    coarse_ix = pool(ix.coarse_factor)
    index = CascadeIndex(dim=cfg.proj_dim, coarse_factor=ix.coarse_factor,
                         fine_factor=ix.fine_factor,
                         candidates=ix.candidates,
                         doc_maxlen=ix.doc_maxlen)
    coarse_docs, raw = coarse_ix.encode_and_pool_counted(docs)
    index.add(coarse_docs, pool(ix.fine_factor).encode_and_pool(docs))
    if out_dir is not None:
        manifest = index.save(out_dir, extra_meta=_spec_extra_meta(spec))
        index_bytes = persist.artifact_bytes(manifest)
    else:
        index_bytes = persist.serialized_nbytes(index)
    stats = IndexStats(n_docs=index.n_docs, n_vectors_raw=raw,
                       n_vectors_stored=index.n_vectors(),
                       index_bytes=index_bytes)
    if out_dir is not None:
        _write_stats(out_dir, stats)
    return index, stats


# (Re)register the stock backends WITH their facade builders — spec.py
# registered the names/kinds/keys import-free; this module owns the
# build recipes.
for _b in ("flat", "hnsw", "plaid"):
    register_backend(_b, "multi_vector_index", INDEX_PARAM_KEYS,
                     builder=_build_multi_vector, overwrite=True)
register_backend("cascade", "cascade_index", CASCADE_PARAM_KEYS,
                 builder=_build_cascade, overwrite=True)


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------
class Retriever:
    """One object from corpus to serving: the stable public API.

    Construction:
      * :meth:`build`  — encode + pool + index (+ save) from a typed
        :class:`~repro.core.spec.RetrieverSpec`;
      * :meth:`load`   — mmap an artifact directory; the build-time
        spec is reconstructed from the manifest.

    Query side: :meth:`search` / :meth:`search_batch` /
    :meth:`rankings` (bitwise equal to the underlying
    ``Searcher``/``MultiVectorIndex`` paths), :meth:`serve` for the
    concurrent runtime, :attr:`stats` for footprint numbers, and
    :meth:`add` / :meth:`delete` for CRUD.
    """

    def __init__(self, params, cfg, index, spec=None,
                 stats: Optional[IndexStats] = None,
                 encode_batch: int = 64):
        self.params = params
        self.cfg = cfg
        self.spec = RetrieverSpec.coerce(spec, cfg)
        self.encode_batch = int(encode_batch)
        self.searcher = Searcher(params, cfg, index,
                                 encode_batch=encode_batch)
        self._stats = stats

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def build(cls, params, cfg, docs, spec=None,
              out_dir: Optional[str] = None,
              encode_batch: int = 64) -> "Retriever":
        """Encode ``docs`` (one [N, L] token array, or an iterator of
        token batches when ``spec.shard`` streams), pool them per
        ``spec.pooling``, build ``spec.index.backend``'s index, and —
        with ``out_dir`` — publish the artifact + ``stats.json``.

        ``spec`` may be a full :class:`RetrieverSpec`, a bare
        :class:`IndexSpec`/:class:`PoolingSpec`/:class:`ShardSpec`
        (the rest defaults from ``cfg``), or None (all from ``cfg``).
        ``encode_batch`` docs go through the encoder at a time, in the
        build and in the searcher's encodes.
        """
        spec = RetrieverSpec.coerce(spec, cfg)
        info = backend_info(spec.index.backend)
        if info.builder is None:
            raise ValueError(f"backend {spec.index.backend!r} has no "
                             f"registered builder")
        index, stats = info.builder(params, cfg, docs, spec, out_dir,
                                    encode_batch)
        return cls(params, cfg, index, spec, stats=stats,
                   encode_batch=encode_batch)

    @classmethod
    def load(cls, params, cfg, path: str, mmap: bool = True,
             serve: Optional[ServeSpec] = None,
             encode_batch: int = 64) -> "Retriever":
        """Serve a persisted artifact directory (any kind — monolithic,
        sharded, cascade): no corpus encode, no index build, payloads
        stay on disk until first search. The spec the index was built
        with comes back off the manifest (``r.spec``); serving knobs
        are runtime-only, so pass ``serve`` to override the default."""
        from repro.core import persist
        manifest = persist.read_manifest(path)
        try:
            spec = retriever_spec_from_manifest(manifest, serve=serve)
        except ValueError as e:
            raise persist.IndexFormatError(str(e))
        index = persist.load_artifact(path, mmap=mmap)
        stats = cls._load_stats(path)
        return cls(params, cfg, index, spec, stats=stats,
                   encode_batch=encode_batch)

    @staticmethod
    def _load_stats(path: str) -> Optional[IndexStats]:
        sp = os.path.join(path, "stats.json")
        if not os.path.isfile(sp):
            return None
        try:
            with open(sp) as fh:
                d = json.load(fh)
        except (OSError, json.JSONDecodeError):
            return None
        known = {f.name for f in dataclasses.fields(IndexStats)}
        return IndexStats(**{k: v for k, v in d.items() if k in known})

    def save(self, out_dir: str) -> dict:
        """Publish the current index as an artifact (re-saves bump the
        manifest generation, so a serving engine watching ``out_dir``
        hot-swaps it in). Returns the manifest."""
        manifest = self.index.save(out_dir,
                                   extra_meta=_spec_extra_meta(self.spec))
        if self._stats is not None:
            _write_stats(out_dir, self._stats)
        return manifest

    # ---------------------------------------------------------------- query
    @property
    def index(self):
        return self.searcher.index

    def search(self, query_tokens: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        """[Nq, L] raw token ids -> (scores [Nq, k], doc ids [Nq, k])."""
        return self.searcher.search(query_tokens, k=k)

    # a Retriever search is always batched (same alias the Searcher has)
    search_batch = search

    def rankings(self, query_tokens: np.ndarray, k: int = 10
                 ) -> List[List[int]]:
        return self.searcher.rankings(query_tokens, k=k)

    def evaluate(self, dataset, metrics=("ndcg@10",), k: int = 10):
        """Score this retriever against an evaluation dataset.

        ``dataset`` is a :class:`repro.eval.datasets.EvalDataset`
        (synthetic or BEIR-loaded); ``metrics`` are ``"<name>@<k>"``
        strings (``ndcg``/``recall``/``success``/``mrr``). Runs ONE
        batched search at depth ``max(k, metric ks)`` and feeds the
        ``[Nq, k]`` ranked-id matrix straight into the batched device
        metrics (``repro.eval.metrics``). Returns ``{name: value}``.
        """
        from repro.eval.metrics import compute_metrics, max_k
        depth = max(int(k), max_k(metrics))
        _, ids = self.search(dataset.query_tokens, k=depth)
        return compute_metrics(ids, dataset.qrels, metrics)

    def warmup(self, batch_sizes: Union[int, Iterable[int]],
               k: int = 10) -> None:
        self.searcher.warmup(batch_sizes, k=k)

    def serve(self, spec: Optional[ServeSpec] = None,
              index_dir: Optional[str] = None,
              index_generation: Optional[int] = None):
        """The concurrent serving runtime (launch/engine.py) over this
        retriever, configured by ``spec`` (default: the build spec's
        ``serve`` block). Use as a context manager; pass ``index_dir``
        to watch an artifact directory for hot swaps."""
        from repro.launch.engine import ServingEngine
        return ServingEngine.from_spec(
            self.searcher, spec or self.spec.serve, index_dir=index_dir,
            index_generation=index_generation)

    # ----------------------------------------------------------------- CRUD
    def _encode_pool(self, doc_tokens: np.ndarray,
                     factor: int) -> List[np.ndarray]:
        ix = self.spec.index
        enc_spec = (ix if ix.backend != "cascade"
                    else IndexSpec.from_config(self.cfg, backend="flat",
                                               doc_maxlen=ix.doc_maxlen))
        return Indexer(self.params, self.cfg, index_spec=enc_spec,
                       pooling_spec=self.spec.pooling.replace(
                           factor=max(int(factor), 1)),
                       encode_batch=self.encode_batch
                       ).encode_and_pool(doc_tokens)

    def add(self, doc_tokens: np.ndarray) -> np.ndarray:
        """Encode + pool + append new documents; returns their doc ids
        (cascade pools each new doc at both levels)."""
        toks = _as_token_array(doc_tokens)
        ix = self.spec.index
        self._stats = None              # CRUD invalidates cached stats
        if ix.backend == "cascade":
            return self.index.add(
                self._encode_pool(toks, ix.coarse_factor),
                self._encode_pool(toks, ix.fine_factor))
        return self.index.add(
            self._encode_pool(toks, self.spec.pooling.factor))

    def delete(self, doc_ids) -> None:
        fn = getattr(self.index, "delete", None)
        if fn is None:
            raise NotImplementedError(
                f"{type(self.index).__name__} does not support delete")
        self._stats = None              # CRUD invalidates cached stats
        fn(doc_ids)

    # ----------------------------------------------------------------- stats
    @property
    def stats(self) -> IndexStats:
        """Build-time stats when available (also loaded back off the
        artifact's ``stats.json``); otherwise synthesized from the live
        index (raw count unknown after a bare load -> 0)."""
        if self._stats is None:
            from repro.core import persist
            index = self.index
            if hasattr(index, "shards"):
                nbytes = sum(persist.serialized_nbytes(s)
                             for s in index.shards)
            else:
                nbytes = persist.serialized_nbytes(index)
            self._stats = IndexStats(
                n_docs=int(index.n_docs), n_vectors_raw=0,
                n_vectors_stored=int(index.n_vectors()),
                index_bytes=int(nbytes),
                n_shards=int(getattr(index, "n_shards", 1)))
        return self._stats
