"""BEYOND-PAPER: pooled-cascade retrieval.

The paper stores ONE pooled representation per document (factor f) and
searches it directly. Observation: pooling quality degrades slowly while
cost drops linearly in f — so an aggressive pool (f=4..8) makes an
excellent *candidate generator*, and a mild pool (f=1..2) an excellent
*reranker*. The cascade stores both:

  stage 1: MaxSim over the COARSE vectors for every doc (4-8x cheaper
           than unpooled full scan) -> top-C candidates per query
  stage 2: exact MaxSim over the FINE vectors of the C candidates only.

Total vector budget: n/f_coarse + n/f_fine vs n for the unpooled index —
e.g. f=(6,2) stores 67% of the vectors but scans only ~17% per query at
full-corpus stage-1. Quality approaches the fine index (measured in
benchmarks/cascade_bench.py); this is the paper's own intuition applied
twice, composed with none of its machinery changed.

Both stages run on the batched two-stage engine: each pool level lives
in a device-resident ``DocStore`` and the whole query batch goes through
one all-pairs stage-1 matmul and one gathered stage-2 rerank — no
per-query Python loop.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.docstore import DocStore
from repro.core.maxsim import (maxsim_all_docs, maxsim_rerank,
                               topk_dispatch)


@dataclass
class CascadeIndex:
    dim: int
    coarse_factor: int = 6
    fine_factor: int = 2
    candidates: int = 32
    doc_maxlen: int = 256

    def __post_init__(self):
        self._coarse = DocStore(self.dim, self.doc_maxlen)
        self._fine = DocStore(self.dim, self.doc_maxlen)

    @property
    def n_docs(self) -> int:
        return self._coarse.n_docs

    # compat views over the stores
    @property
    def coarse_docs(self) -> List[np.ndarray]:
        return self._coarse.docs_list()

    @property
    def fine_docs(self) -> List[np.ndarray]:
        return self._fine.docs_list()

    def add(self, coarse: List[np.ndarray], fine: List[np.ndarray]):
        assert len(coarse) == len(fine)
        ids = self._coarse.add(coarse)
        self._fine.add(fine)
        return ids

    # ------------------------------------------------------------ persistence
    def save(self, path: str, extra_meta: dict = None) -> dict:
        """Write both pool levels as one artifact dir (core/persist.py)."""
        from repro.core import persist
        return persist.save_cascade(self, path, extra_meta=extra_meta)

    @classmethod
    def from_dir(cls, path: str, mmap: bool = True) -> "CascadeIndex":
        """Load via the shared kind dispatcher (core/persist.py), so the
        error for a non-cascade artifact names what the directory
        actually holds instead of failing on missing payloads."""
        from repro.core import persist
        obj = persist.load_artifact(path, mmap=mmap)
        if not isinstance(obj, cls):
            raise persist.IndexFormatError(
                f"{path!r} holds a {type(obj).__name__} artifact, not a "
                f"CascadeIndex — load it with persist.load_artifact / "
                f"Searcher.from_dir instead")
        return obj

    def warm_shapes(self, qs: np.ndarray, k: int = 10) -> None:
        """Pre-compile every executable a serving stream at this query
        batch shape can hit — the batched-engine conformance hook
        (``Searcher.warmup`` / ``ServingEngine`` call it per shape
        bucket). Unlike the staged backends, cascade shapes are
        data-INdependent given (Nq, k): stage 1 is one all-pairs matmul
        over the fixed coarse view and stage 2 gathers exactly
        ``min(max(candidates, k), n_docs)`` fine slates — so one
        organic ``search_batch`` traces everything and a mixed stream
        afterwards re-jits nothing (compile-count probe pinned in
        tests/test_api.py)."""
        if self.n_docs == 0:
            return
        self.search_batch(np.asarray(qs, np.float32), k=k)

    def search_batch(self, qs: np.ndarray, k: int = 10, *,
                     fetch: bool = True):
        """qs [Nq, Lq, dim] -> (scores [Nq, k], ids [Nq, k]; -inf/-1 pads).
        ``fetch=False``: the result as dispatched, as
        ``MultiVectorIndex.search_batch`` has it (stage 1's candidate
        ids still come to the host for the stage-2 gather)."""
        qs = jnp.asarray(np.asarray(qs, np.float32))
        Nq = qs.shape[0]
        n = self._coarse.n_docs
        if n == 0:
            return (np.full((Nq, k), -np.inf, np.float32),
                    np.full((Nq, k), -1, np.int64))
        qm = jnp.ones(qs.shape[:2], bool)
        # stage 1: one all-pairs matmul over the coarse corpus view
        cd, cm = self._coarse.padded()
        s1 = maxsim_all_docs(qs, qm, cd, cm)               # [Nq, n]
        C = min(max(self.candidates, k), n)
        _, cand = jax.lax.top_k(s1, C)                     # [Nq, C]
        cand = np.asarray(cand, np.int64)
        # stage 2: gathered exact rerank over the fine vectors
        fd, fm = self._fine.gather(cand)
        s2 = maxsim_rerank(qs, qm, fd, fm)                 # [Nq, C]
        out = topk_dispatch(s2, cand, k)
        return out.fetch() if fetch else out

    def search(self, q: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        """q [Lq, dim] -> (scores [<=k], ids [<=k])."""
        S, I = self.search_batch(np.asarray(q, np.float32)[None], k=k)
        valid = I[0] >= 0
        return S[0][valid], I[0][valid]

    def n_vectors(self) -> int:
        return (self._coarse.n_vectors(live_only=False)
                + self._fine.n_vectors(live_only=False))

    def stage1_vectors(self) -> int:
        """Vectors touched by a full stage-1 scan (the per-query cost)."""
        return self._coarse.n_vectors(live_only=False)


def build_cascade(indexer_params, cfg, doc_tokens: np.ndarray,
                  coarse_factor: int = 6, fine_factor: int = 2,
                  candidates: int = 32,
                  pool_method: str = "ward") -> CascadeIndex:
    """Encode once, pool twice (coarse + fine), build the cascade.
    ``pool_method`` resolves through the spec layer's strategy registry
    (core/spec.py), so registered policies work at both levels."""
    from repro.retrieval.indexer import Indexer
    coarse = Indexer(indexer_params, cfg, pool_method=pool_method,
                     pool_factor=coarse_factor,
                     backend="flat").encode_and_pool(doc_tokens)
    fine = Indexer(indexer_params, cfg, pool_method=pool_method,
                   pool_factor=fine_factor,
                   backend="flat").encode_and_pool(doc_tokens)
    idx = CascadeIndex(dim=cfg.proj_dim, coarse_factor=coarse_factor,
                       fine_factor=fine_factor, candidates=candidates,
                       doc_maxlen=cfg.doc_maxlen)
    idx.add(coarse, fine)
    return idx
