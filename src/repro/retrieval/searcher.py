"""Searcher: query encode -> staged candidate generation -> rerank.

Query-time is UNCHANGED by token pooling (the paper's key deployment
property): the searcher is identical for pooled and unpooled indexes.

The query path is two STATELESS stages the serving runtime
(launch/engine.py) pipelines independently:

  * ``encode_queries``  [Nq, L] token ids -> [Nq, Lq, dim] vectors —
    chunks pad up to the nearest power-of-two width (capped at
    ``encode_batch``), so a mixed stream of request sizes reuses
    log-many executables and a 2-query microbatch never pays a
    64-wide encoder pass. Each output row depends only on its input
    row AND is bitwise independent of the padded width (pinned by
    tests), so a query's vectors are identical however it was
    coalesced;
  * ``search_encoded``  encoded vectors -> (scores, ids) through the
    index's batched two-stage engine.

``search``/``search_batch`` chain the two for the whole batch in one
call (one traced rerank per microbatch, no per-query loop). ``warmup``
triggers jit compilation for a batch size — or a whole LIST of shape
buckets — so serving latency percentiles exclude compile time and a
bucketed batcher never re-traces mid-stream.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ColbertConfig
from repro.core.index import MultiVectorIndex
from repro.models.colbert import encode_queries


class Searcher:
    def __init__(self, params, cfg: ColbertConfig,
                 index, encode_batch: int = 64):
        # index: anything with the batched two-stage search interface —
        # MultiVectorIndex, ShardedIndex, or CascadeIndex
        self.params = params
        self.cfg = cfg
        self.index = index
        self.encode_batch = encode_batch

    @classmethod
    def from_dir(cls, params, cfg: ColbertConfig, path: str,
                 mmap: bool = True, encode_batch: int = 64) -> "Searcher":
        """Serve a persisted index artifact: no corpus encode, no index
        build — the document payloads stay on disk until first search.

        Dispatches on the artifact's manifest ``kind``, so monolithic
        and sharded (and cascade) index directories serve through the
        same API."""
        from repro.core.persist import load_artifact
        return cls(params, cfg, load_artifact(path, mmap=mmap),
                   encode_batch=encode_batch)

    def _encode_width(self, n: int) -> int:
        """Smallest power-of-two device width holding ``n`` queries,
        capped at ``encode_batch`` — the encoder's shape buckets."""
        w = 1
        while w < n and w < self.encode_batch:
            w <<= 1
        return min(w, self.encode_batch)

    def encode_queries(self, query_tokens: np.ndarray) -> np.ndarray:
        """[Nq, L] -> [Nq, Lq, dim] (all expansion slots emit).

        Stateless stage 1 of the serving pipeline: chunks of up to
        ``encode_batch`` queries pad to the nearest power-of-two width,
        so log-many traced shapes serve any request size, and a row's
        output never depends on what it was batched with (nor on the
        padded width — encoder rows are bitwise width-stable)."""
        out = []
        N = query_tokens.shape[0]
        B = self.encode_batch
        h2d = d2h = 0
        with obs.span(obs.ENCODER_QUERIES) as sp:
            for lo in range(0, N, B):
                chunk = query_tokens[lo:lo + B]
                n = chunk.shape[0]
                pad = self._encode_width(n) - n
                if pad:
                    chunk = np.pad(chunk, ((0, pad), (0, 0)))
                h2d += obs.host_nbytes(chunk)
                v, _ = encode_queries(self.params, jnp.asarray(chunk),
                                      self.cfg)
                v = np.asarray(v)
                d2h += v.nbytes
                out.append(v[:n] if pad else v)
            sp.set_metadata(h2d_bytes=h2d, d2h_bytes=d2h)
        return np.concatenate(out)

    def encode(self, query_tokens: np.ndarray) -> np.ndarray:
        """DEPRECATED alias predating the stage split — use
        :meth:`encode_queries` (the name the spec-era public API,
        ``repro.Retriever``, and the serving engine pipeline use)."""
        import warnings
        warnings.warn("Searcher.encode is deprecated; use "
                      "Searcher.encode_queries", DeprecationWarning,
                      stacklevel=2)
        return self.encode_queries(query_tokens)

    def search(self, query_tokens: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        """[Nq, L] raw token ids -> (scores [Nq, k], doc ids [Nq, k])."""
        return self.search_encoded(self.encode_queries(query_tokens), k=k)

    def search_encoded(self, query_vectors: np.ndarray, k: int = 10
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Pre-encoded [Nq, Lq, dim] -> (scores [Nq, k], ids [Nq, k])."""
        return self.index.search_batch(query_vectors, k=k)

    # alias: a Searcher search is always batched
    search_batch = search

    def rankings(self, query_tokens: np.ndarray, k: int = 10
                 ) -> List[List[int]]:
        _, ids = self.search(query_tokens, k)
        return [[int(d) for d in row if d >= 0] for row in ids]

    def warmup(self, batch_sizes: Union[int, Iterable[int]],
               k: int = 10) -> None:
        """Trace/compile the serving pipeline for one or many shapes.

        Pass a single batch size (legacy) or the batcher's full list of
        shape buckets: BOTH stages compile per bucket — the encoder at
        each power-of-two width, ``search_encoded`` at every requested
        batch size — so a mixed stream of microbatch shapes served
        afterwards hits only warm executables (the no-retrace property
        tests/test_serving_engine.py pins with a compile-count probe).
        """
        if isinstance(batch_sizes, (int, np.integer)):
            batch_sizes = [int(batch_sizes)]
        sizes = sorted({int(b) for b in batch_sizes})
        if not sizes:
            return
        L = self.cfg.query_maxlen - 2
        warm = getattr(self.index, "warm_shapes", None)
        for bs in sizes:
            enc = self.encode_queries(np.ones((bs, L), np.int32))
            if warm is not None:
                # also traces the data-dependent candidate-width ladder
                # (a width first seen mid-stream would compile in-band)
                warm(enc, k=k)
            else:
                self.search_encoded(enc, k=k)
