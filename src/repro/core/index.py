"""Index facade: Flat | HNSW | PLAID behind one add/delete/search interface.

The paper's two experimental settings map to:
  * ``hnsw``  — 16-bit unpooled/pooled vectors in a token-level HNSW graph
                (paper uses VOYAGER); stage 2 exact rerank over stored vectors.
  * ``plaid`` — 2-bit residual-quantized vectors behind IVF probing.
  * ``flat``  — exact MaxSim over everything (the oracle; small corpora only).

All three store *token* vectors grouped by document and return document ids,
so the evaluation harness is backend-agnostic. Pooling happens upstream
(retrieval/indexer.py) — the index only ever sees the (possibly pooled)
per-document vector lists. CRUD: ``add`` appends docs, ``delete`` removes
them (all backends delete lazily; compaction via rebuild).

Serving is a batched two-stage engine over a device-resident ``DocStore``:

    candidates(qs)  -> per-query candidate doc ids   (stage 1, backend-specific)
    rerank(qs, ...) -> exact MaxSim on the gathered candidates (stage 2, shared)

Stage 1 is batched centroid probing (PLAID: one einsum for the whole
batch), batched HNSW token probes with a vectorized candidate-set union,
or — for flat — the whole live corpus. Stage 2 is ONE fixed-shape MaxSim
batch per query microbatch (the Pallas ``kernels/maxsim`` op on TPU, its
jnp oracle elsewhere); no backend re-pads the corpus at query time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.docstore import DocStore, pad_candidate_sets
from repro.core.hnsw import HNSW
from repro.core.ivf import train_centroids
from repro.core.maxsim import (maxsim_all_docs, maxsim_rerank_store,
                               topk_dispatch)
from repro.core.plaid import (PLAIDIndex, PROBE_KERNELS, build_plaid_index,
                              device_probe_plan, maxsim_packed_rerank_store,
                              plaid_candidates)
from repro.core.quantization import train_codec
from repro.core.spec import INDEX_PARAM_KEYS

BACKENDS = ("flat", "hnsw", "plaid")

# Construction knobs shared by persistence (manifest params) and sharding
# (per-shard construction). The defining copy lives in core/spec.py —
# the typed spec layer every surface (Indexer, manifests, CLI) derives
# from; this re-export keeps the long-standing import site working.
PARAM_KEYS = INDEX_PARAM_KEYS


@dataclass
class MultiVectorIndex:
    """Late-interaction index over per-document token-vector lists."""
    dim: int
    backend: str = "plaid"
    doc_maxlen: int = 256
    # PLAID params
    n_centroids: int = 256
    quant_bits: int = 2
    nprobe: int = 8
    t_cs: float = 0.3
    ndocs: int = 8192
    # HNSW params (paper Appendix A)
    hnsw_m: int = 12
    hnsw_ef_construction: int = 200
    hnsw_candidates: int = 1024    # token hits gathered before doc rerank
    # Serving toggle (not a construction param; never persisted): plaid
    # rerank straight from packed codes vs. the legacy f32 reconstruction
    # store. Both produce bitwise-identical scores — False exists for the
    # parity tests and for debugging against the decoded view.
    packed_rerank: bool = True
    # Serving toggle (RUNTIME-ONLY, never persisted — same contract as
    # ``packed_rerank``): plaid candidate generation on device
    # ("auto"/"device", see ``plaid.device_probe_plan``) vs the host
    # numpy reference ("host"). Both produce bitwise-identical slates.
    probe_kernel: str = "auto"

    # state
    deleted: set = field(default_factory=set)
    _store: Optional[DocStore] = None
    _hnsw: Optional[HNSW] = None
    _hnsw_vec2doc: Optional[np.ndarray] = None
    _plaid: Optional[PLAIDIndex] = None
    _preset_codec: Optional[object] = field(default=None, repr=False)
    _live_dev_cache: Optional[jnp.ndarray] = field(default=None, repr=False)

    def __post_init__(self):
        assert self.backend in BACKENDS, self.backend
        assert self.probe_kernel in PROBE_KERNELS, self.probe_kernel
        if self.backend != "plaid":
            self._store = DocStore(self.dim, self.doc_maxlen)

    # ------------------------------------------------------------ doc store
    @property
    def store(self) -> DocStore:
        """The DocStore dense/corpus-wide scoring reads from.

        flat/hnsw: the raw stored vectors; plaid: the decoded
        reconstruction CACHE — touching this property materializes it
        (O(corpus) decode + f32 residency), which the packed candidate
        rerank never does. Only the dense path (cand width >= n_docs;
        tiny corpora) and debug/compat views should land here.
        """
        if self.backend == "plaid":
            assert self._plaid is not None, "empty plaid index"
            return self._plaid.recon_store()
        return self._store

    @property
    def n_docs(self) -> int:
        if self.backend == "plaid":
            return self._plaid.n_docs if self._plaid is not None else 0
        return self._store.n_docs

    @property
    def docs(self) -> List[np.ndarray]:
        """Compat view: per-doc vector arrays (deleted docs included).

        NOTE: for the plaid backend these are the codec's decoded
        *reconstructions* (what rerank scores), not the raw inputs —
        the raw vectors are not retained; first access also builds the
        reconstruction store (O(corpus) decode).
        """
        if self.backend == "plaid":
            return (self.store.docs_list() if self._plaid is not None
                    else [])
        return self._store.docs_list()

    def _live(self) -> np.ndarray:
        """[n_docs] bool — True for docs that can still be returned.

        flat/hnsw read the DocStore's live mask (single source of truth,
        shared with nbytes/n_vectors); plaid keeps no raw store, so its
        liveness comes from the ``deleted`` set.
        """
        if self._store is not None:
            return self._store.live.copy()
        live = np.ones(self.n_docs, bool)
        if self.deleted:
            live[np.fromiter(self.deleted, np.int64)] = False
        return live

    def _live_dev(self) -> jnp.ndarray:
        """Device-cached live mask for the zero-hop candidate path —
        shipped once per mutation epoch instead of once per query."""
        if self._live_dev_cache is None:
            self._live_dev_cache = jnp.asarray(self._live())
        return self._live_dev_cache

    def _probe_plan(self, Lq: int):
        """The device candidate-path decision for this query length
        (see ``plaid.device_probe_plan``)."""
        if self.backend != "plaid" or self._plaid is None:
            return False, None
        return device_probe_plan(self._plaid, Lq, self.nprobe, self.ndocs,
                                 self.probe_kernel, t_cs=self.t_cs)

    # ------------------------------------------------------------------ build
    def add(self, doc_vectors: List[np.ndarray]) -> np.ndarray:
        """doc_vectors: list of [n_i, dim] unit vectors. Returns doc ids."""
        if len(doc_vectors) == 0:
            return np.zeros((0,), np.int64)     # no-op on every backend
        doc_vectors = [np.asarray(v, np.float32).reshape(-1, self.dim)
                       for v in doc_vectors]
        ids = np.arange(self.n_docs, self.n_docs + len(doc_vectors))
        if self.backend == "hnsw":
            self._store.add(doc_vectors)
            self._add_hnsw(doc_vectors, ids)
        elif self.backend == "plaid":
            self._add_plaid(doc_vectors)
        else:
            self._store.add(doc_vectors)
        self._live_dev_cache = None
        return ids

    def _add_hnsw(self, doc_vectors, ids):
        if self._hnsw is None:
            self._hnsw = HNSW(self.dim, m=self.hnsw_m,
                              ef_construction=self.hnsw_ef_construction)
            self._hnsw_vec2doc = np.zeros((0,), np.int64)
        flat = np.concatenate(doc_vectors)
        self._hnsw.add(flat)
        lens = np.array([len(v) for v in doc_vectors], np.int64)
        self._hnsw_vec2doc = np.concatenate(
            [self._hnsw_vec2doc, np.repeat(ids, lens)])

    def set_codec(self, codec) -> None:
        """Preset the plaid residual codec instead of training on the
        first ``add``. This is how shards of one logical index share ONE
        quantization model (core/sharded.py): identical centroids make
        per-shard candidate generation equivalent to monolithic probing,
        and identical reconstructions make merged scores comparable
        bit-for-bit across shards."""
        assert self.backend == "plaid", self.backend
        assert self._plaid is None, "codec must be preset before add"
        self._preset_codec = codec

    def _add_plaid(self, doc_vectors):
        # bytes: the pooled vectors go to the device once for the codec
        # encode (twice more when the codec is trained here); their ids
        # and packed codes come back
        moved = sum(v.nbytes for v in doc_vectors)
        held = self._plaid_code_bytes()
        with obs.span(obs.PLAID_ADD) as sp:
            h2d = moved
            if self._plaid is None:
                if self._preset_codec is not None:
                    codec = self._preset_codec
                else:
                    flat = np.concatenate(doc_vectors)
                    k = min(self.n_centroids, len(flat))
                    centroids = train_centroids(flat, k)
                    codec = train_codec(jnp.asarray(flat), centroids,
                                        bits=self.quant_bits)
                    h2d += 2 * flat.nbytes
                self._plaid = build_plaid_index(doc_vectors, codec,
                                                self.doc_maxlen)
            else:
                self._plaid.add(doc_vectors)
            sp.set_metadata(h2d_bytes=h2d,
                            d2h_bytes=self._plaid_code_bytes() - held)

    def _plaid_code_bytes(self) -> int:
        """Host bytes of the plaid centroid ids and packed codes."""
        p = self._plaid
        return 0 if p is None else p.assignments.nbytes + p.codes.nbytes

    # ------------------------------------------------------------ persistence
    def save(self, path: str, extra_meta: Optional[dict] = None) -> dict:
        """Write a versioned artifact directory (core/persist.py);
        lazily-deleted docs are compacted out of the payload bytes.
        Returns the manifest."""
        from repro.core import persist
        return persist.save_index(self, path, extra_meta=extra_meta)

    @classmethod
    def load(cls, path: str, mmap: bool = True) -> "MultiVectorIndex":
        """Reconstruct an index from ``save``'s directory. With
        ``mmap=True`` the payloads stay on disk (zero-copy) until the
        first search touches them."""
        from repro.core import persist
        return persist.load_index(path, mmap=mmap)

    def delete(self, doc_ids) -> None:
        self.deleted.update(int(i) for i in doc_ids)
        if self.backend == "hnsw" and self._hnsw is not None:
            tok = np.nonzero(np.isin(self._hnsw_vec2doc,
                                     np.asarray(doc_ids)))[0]
            self._hnsw.delete(tok)
        if self._store is not None:
            self._store.delete(np.asarray(doc_ids, np.int64))
        self._live_dev_cache = None
        # plaid filters deleted ids at candidate time (compaction = rebuild)

    # ------------------------------------------------- two-stage batch engine
    def candidates(self, qs: np.ndarray,
                   q_mask: Optional[np.ndarray] = None
                   ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
        """Stage 1: qs [Nq, Lq, dim] -> (cand [Nq, C], mask [Nq, C]).

        Returns ``(None, None)`` for the flat backend: every live doc is
        a candidate and rerank scores the shared corpus view directly
        (an all-pairs matmul beats an Nq-fold gather of the corpus).
        Masked query tokens (q_mask False) are excluded from probing and
        approximate scoring, matching the rerank-stage semantics.
        """
        if self.backend == "flat":
            return None, None
        if self.backend == "plaid":
            use_dev, _ = self._probe_plan(np.asarray(qs).shape[1])
            live = self._live_dev() if use_dev else self._live()
            return plaid_candidates(self._plaid, qs, nprobe=self.nprobe,
                                    t_cs=self.t_cs, ndocs=self.ndocs,
                                    live=live, q_mask=q_mask,
                                    probe_kernel=self.probe_kernel)
        return self._hnsw_candidates(qs, q_mask)

    def _hnsw_candidates(self, qs: np.ndarray, q_mask=None):
        """Batched token probes + vectorized candidate-set union."""
        Nq, Lq = qs.shape[:2]
        per_tok = max(self.hnsw_candidates // max(Lq, 1), 8)
        vec_ids = self._hnsw.probe_tokens(
            np.asarray(qs, np.float32).reshape(Nq * Lq, self.dim), per_tok)
        hit = vec_ids >= 0                                 # [Nq*Lq, per_tok]
        if q_mask is not None:     # masked tokens probe nothing
            hit &= np.asarray(q_mask, bool).reshape(Nq * Lq, 1)
        qidx = np.repeat(np.arange(Nq), Lq * per_tok)[hit.ravel()]
        docs = self._hnsw_vec2doc[vec_ids[hit]]
        qd = np.unique(qidx * np.int64(max(self.n_docs, 1)) + docs)
        qidx, docs = qd // max(self.n_docs, 1), qd % max(self.n_docs, 1)
        live = self._live()
        keep = live[docs]
        return pad_candidate_sets(qidx[keep], docs[keep], Nq)

    def rerank(self, qs: np.ndarray, cand: Optional[np.ndarray] = None,
               cand_mask: Optional[np.ndarray] = None,
               q_mask: Optional[np.ndarray] = None) -> jnp.ndarray:
        """Stage 2 (shared): exact MaxSim on gathered candidates.

        One traced fixed-shape batch per call; invalid/padded candidate
        slots come back as -inf. ``cand=None`` scores the whole live
        corpus (scores [Nq, n_docs]); otherwise scores [Nq, C].
        """
        if (cand is not None and self.backend == "plaid"
                and self._plaid is not None and self.packed_rerank):
            # host query arrays go to the device inside, under its span
            return maxsim_packed_rerank_store(self._plaid, qs, q_mask,
                                              cand, cand_mask)
        qs = jnp.asarray(qs, jnp.float32)
        qm = (jnp.ones(qs.shape[:2], bool) if q_mask is None
              else jnp.asarray(q_mask))
        if cand is None:
            # corpus-wide dense scoring stays on the f32 view: at this
            # width the decoded store is read Nq times per batch, so the
            # one-off reconstruction cache pays for itself (tiny-corpus
            # regime — see README "Compressed-domain rerank")
            d, dm = self.store.padded()
            scores = maxsim_all_docs(qs, qm, d, dm)        # [Nq, n_docs]
            return jnp.where(jnp.asarray(self._live())[None, :],
                             scores, -jnp.inf)
        if not isinstance(cand, np.ndarray):    # legacy store path is
            cand = np.asarray(cand, np.int64)   # host-indexed
            cand_mask = np.asarray(cand_mask)
        return maxsim_rerank_store(self.store, qs, qm, cand, cand_mask)

    def _rerank_dense(self, qs, cand, cand_mask, q_mask) -> jnp.ndarray:
        """Dense-candidate rerank: when the padded candidate width reaches
        corpus size, an Nq-fold gather repeats most of the corpus per
        query — one shared all-pairs scan + a membership mask is cheaper.
        Returns scores [Nq, n_docs] (-inf outside each query's set)."""
        scores = self.rerank(qs, None, None, q_mask)   # [Nq, n_docs]
        member = np.zeros((len(cand), self.n_docs), bool)
        rows = np.repeat(np.arange(len(cand)),
                         cand.shape[1])[np.asarray(cand_mask).ravel()]
        member[rows, cand[cand_mask]] = True
        return jnp.where(jnp.asarray(member), scores, -jnp.inf)

    def scored_candidates(self, qs: np.ndarray,
                          q_mask: Optional[np.ndarray] = None
                          ) -> Tuple[jnp.ndarray, Optional[np.ndarray]]:
        """Both stages, no top-k: the per-index *scored slate*.

        Returns ``(scores [Nq, C], cand [Nq, C] | None)`` — exact MaxSim
        for every surviving candidate, -inf on invalid slots. ``cand``
        is None when the scores are corpus-wide (ids = column index):
        the flat backend, or a candidate set grown to corpus width
        (dense rerank beats an Nq-fold gather there). This is the unit
        ``ShardedIndex`` fans out per shard before its global merge;
        ``search_batch`` is just slate -> top-k.

        Within each query row, finite slots are ordered by ascending doc
        id (column index when dense; sorted unique ids otherwise) —
        except after plaid's approximate prune (cand count > ndocs),
        which reorders survivors by approximate score. Under an
        exhaustive candidate budget, top-k tie-breaking is id-stable.
        """
        qs = np.asarray(qs, np.float32)
        cand, cand_mask = self.candidates(qs, q_mask)
        if cand is not None and cand.shape[1] >= self.n_docs:
            return self._rerank_dense(qs, cand, cand_mask, q_mask), None
        return self.rerank(qs, cand, cand_mask, q_mask), cand

    def candidate_widths(self, qs: np.ndarray
                         ) -> Tuple[List[int], bool]:
        """Slate widths a stream at this batch shape can reach.

        Returns ``(widths, dense)``: the geometric pad ladder
        {32, 64, ...} (``pad_candidate_sets``) capped by the stage-1
        candidate budget (plaid: ndocs before the prune; hnsw: the
        token-probe hit bound) plus plaid's post-prune block-padded
        width, RESTRICTED to widths below ``n_docs`` — wider sets
        dispatch to the dense corpus-wide path, whose reachability is
        the ``dense`` flag. The contract ``warm_shapes`` and the
        sharded/replicated merge warms trace against.
        """
        if self.n_docs == 0:
            return [], False
        if self.backend == "flat":
            return [], True                 # dense only
        qs = np.asarray(qs, np.float32)
        block = 32                          # pad_candidate_sets block
        if self.backend == "plaid":
            use_dev, geom = self._probe_plan(qs.shape[1])
            if use_dev:
                # device pipeline: ONE static slate width (s_out), and
                # the plan proved the dense dispatch unreachable
                return [geom[3]], False
            cap = min(self.n_docs, self.ndocs)
        else:
            Lq = max(qs.shape[1], 1)
            per_tok = max(self.hnsw_candidates // Lq, 8)
            cap = min(self.n_docs, per_tok * Lq)
        widths = set()
        C = block
        while C < cap:
            widths.add(C)
            C <<= 1
        widths.add(C)                       # first ladder value >= cap
        if self.backend == "plaid":         # post-prune width
            widths.add(-(-min(self.ndocs, self.n_docs) // block) * block)
        return (sorted(w for w in widths if w < self.n_docs),
                max(widths) >= self.n_docs)

    def warm_shapes(self, qs: np.ndarray, k: int = 10) -> None:
        """Pre-compile every executable a serving stream at this query
        batch shape can hit — including the CANDIDATE-width axis.

        ``search_batch`` shapes depend on data: stage 1 yields a padded
        candidate matrix whose width C walks ``candidate_widths`` or
        the dense corpus-wide path once C reaches ``n_docs``. A width
        first seen mid-stream costs an XLA compile (hundreds of ms on
        CPU) that lands straight in some query's tail latency. Serving
        runtimes (launch/engine.py) call this at warmup per shape
        bucket so the whole ladder is traced before traffic."""
        qs = np.asarray(qs, np.float32)
        if self.n_docs == 0:
            return
        self.search_batch(qs, k=k)          # stage-1 + one organic path
        if self.backend == "flat":
            return                          # dense only: already warm
        Nq = len(qs)
        widths, dense = self.candidate_widths(qs)
        for C in widths:
            cand = np.zeros((Nq, C), np.int64)   # doc 0: shape-only work
            mask = np.ones((Nq, C), bool)
            scores = self.rerank(qs, cand, mask)
            topk_dispatch(scores, cand, k).fetch()
        if (self.backend == "plaid" and self._plaid is not None
                and not self._probe_plan(qs.shape[1])[0]):
            # host path only: the device pipeline is ONE executable per
            # (Nq, Lq) — lax.cond traces both prune branches — so the
            # organic search above already compiled everything
            self._warm_plaid_prune(qs)
        if dense:
            # dense corpus-wide fallback is reachable (a candidate set
            # can grow to corpus width) — warm the full dense-candidate
            # path (_rerank_dense: corpus scan + membership mask), not
            # just the bare scan; when the budget caps far below n_docs,
            # skip: it would materialize the whole padded corpus for an
            # executable traffic never hits
            C = max(widths, default=32)
            scores = self._rerank_dense(qs, np.zeros((Nq, C), np.int64),
                                        np.ones((Nq, C), bool), None)
            topk_dispatch(scores, None, k).fetch()

    def _warm_plaid_prune(self, qs: np.ndarray) -> None:
        """Trace plaid's PRE-prune stage-3 shapes for this batch shape.

        When the IVF gather exceeds ``ndocs``, ``plaid_candidates``
        scores candidates centroid-only at the GATHER width — ladder
        values above ``ndocs`` — before pruning; those executables are
        not touched by the rerank ladder warm, so drive them here."""
        import jax
        from repro.core.plaid import (_approx_scores_batch,
                                      _centroid_scores_batch)
        p = self._plaid
        Nq = len(qs)
        block = 32
        cs = _centroid_scores_batch(jnp.asarray(qs, jnp.float32),
                                    jnp.asarray(p.codec.centroids))
        codes, tok_mask = p.padded_codes()
        # gather ladder: 32<<m up to the first value >= n_docs (counts
        # are capped by live docs, but the geometric pad can overshoot)
        C = block
        while True:
            if C > self.ndocs:              # prune engages above budget
                cand = jnp.zeros((Nq, C), jnp.int64)
                cmask = jnp.ones((Nq, C), bool)
                approx = _approx_scores_batch(
                    cs, jnp.take(codes, cand, axis=0),
                    jnp.take(tok_mask, cand, axis=0) & cmask[:, :, None],
                    cmask, self.t_cs)
                jax.lax.top_k(approx, min(self.ndocs, C))
            if C >= self.n_docs:
                break
            C <<= 1

    # ----------------------------------------------------------------- search
    def search_batch(self, qs: np.ndarray, k: int = 10,
                     q_mask: Optional[np.ndarray] = None, *,
                     fetch: bool = True):
        """qs: [Nq, Lq, dim] -> (scores [Nq, k], ids [Nq, k]; -inf/-1 pads).

        ``fetch=False`` returns the result as dispatched (a
        ``PendingTopK`` still in flight, or host arrays where no device
        work is left): unpacking it as ``(scores, ids)`` waits for it
        and gives what ``fetch=True`` returns."""
        qs = np.asarray(qs, np.float32)
        Nq = len(qs)
        if self.n_docs == 0:
            return (np.full((Nq, k), -np.inf, np.float32),
                    np.full((Nq, k), -1, np.int64))
        scores, cand = self.scored_candidates(qs, q_mask)
        out = topk_dispatch(scores, cand, k)
        return out.fetch() if fetch else out

    def search(self, q: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        """q: [Lq, dim] query token vectors -> (scores [k'], doc ids [k'])."""
        S, I = self.search_batch(np.asarray(q, np.float32)[None], k=k)
        valid = I[0] >= 0
        return S[0][valid], I[0][valid]

    # ------------------------------------------------------------------ stats
    def n_vectors(self) -> int:
        if self.n_docs == 0:
            return 0
        if self.backend == "plaid":
            lens = np.diff(self._plaid.doc_offsets)
            return int(lens[self._live()].sum())
        lens = self._store.doc_lengths()
        return int(lens[self._live()].sum())

    def nbytes(self) -> int:
        if self.backend == "hnsw" and self._hnsw is not None:
            return self._hnsw.nbytes()
        if self.backend == "plaid" and self._plaid is not None:
            return self._plaid.nbytes()
        # flat: fp16 store, live docs only (deleted docs are reclaimable)
        return self._store.nbytes(bytes_per_dim=2, live_only=True)

    def device_bytes(self) -> int:
        """Device-resident bytes of the query-time doc representation —
        what serving actually holds in accelerator memory, as opposed to
        ``nbytes`` (the persisted/host index). plaid: packed views +
        codec tables (+ recon cache only while resident); flat/hnsw: the
        padded f32 view."""
        if self.backend == "plaid":
            return self._plaid.device_bytes() if self._plaid is not None \
                else 0
        return self._store.device_nbytes()
