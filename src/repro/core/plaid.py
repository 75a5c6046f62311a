"""PLAID-style staged late-interaction search (Santhanam et al., 2022).

The index the paper composes token pooling with ("2-bit quantization and
PLAID indexing ... with the original codebase", §3.1). Four stages, all
batched over the whole query batch:

  1. **Centroid probe** — every query token of every query scores all K
     centroids in ONE einsum; top-``nprobe`` centroid ids per token form
     the probe set.
  2. **Candidate generation** — vectorized inverted-list gather of the
     vectors owned by probed centroids -> per-query candidate documents
     (host numpy, no per-query Python loop: one repeat/unique sweep over
     the whole batch).
  3. **Approximate scoring** — per candidate doc, MaxSim over its
     *centroid ids only* (no decompression), centroid scores below
     ``t_cs`` pruned to 0; a jit-compiled scan over candidate blocks.
     Top-``ndocs`` docs per query survive.
  4. **Exact rerank** — survivors' PACKED rows (centroid ids + residual
     words) are gathered and scored in the compressed domain: the fused
     Pallas kernel (kernels/maxsim_packed) unpacks, reconstructs and
     renormalizes per VMEM tile on TPU; off-TPU the gathered rows are
     decoded eagerly and fed to the same ``maxsim_rerank`` dispatcher,
     bitwise-matching the old reconstruction path. The f32
     reconstruction ``DocStore`` is now a lazy cache built only on
     demand (corpus-wide dense scoring, debugging) — packed serving
     never materializes it.

Query hyperparameters default to the best PLAID reproduction-study settings
the paper uses (Appendix A): nprobe=8, t_cs=0.3, ndocs=8192.

Device/host split: matmul-shaped stages (1, 3, 4) are jit'd jnp/Pallas;
list bookkeeping (2) has two interchangeable implementations — the
vectorized host-numpy reference, and a fully DEVICE-RESIDENT pipeline
(``probe_kernel`` toggle) that runs stages 1-3 as ONE fixed-shape jit
program: padded per-centroid doc-list gather from ``DeviceInvertedLists``,
sort-based (query, doc) dedupe, and the centroid-interaction probe
(``kernels/plaid_probe``: every doc scored doc-major from its centroid
bag) behind a ``lax.cond`` prune — no ``np.asarray`` host hop between
query encode and the final top-k. The device path is engaged only when
it is provably bitwise-equal to the host path (exact IVF view, no
negative prune threshold, no doc cut by ``doc_maxlen``, dense
corpus-wide regime statically unreachable). Fixed shapes throughout:
candidate sets are padded to a block multiple so stage 3/4 trace once
per (batch size, candidate budget) pair.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.docstore import (DocStore, pad_candidate_sets,
                                 ragged_arange)
from repro.core.ivf import (DeviceInvertedLists, InvertedLists,
                            build_device_inverted_lists,
                            build_inverted_lists)
from repro.core.maxsim import _on_tpu, maxsim_rerank, topk_dispatch
from repro.core.quantization import ResidualCodec, decode, encode
from repro.kernels.plaid_probe.ref import fold_sum

_CAND_BLOCK = 32       # candidate-axis padding granularity (jit shape reuse)
PROBE_KERNELS = ("auto", "device", "host")
# auto mode falls back to the host gather above this membership-table
# size (K * n_docs f32 elements) — the dense union matmul would
# dominate device memory; "device" forces through it.
_DEVICE_GATHER_CAP = 1 << 24


@dataclass
class PLAIDIndex:
    codec: ResidualCodec
    ivf: InvertedLists
    assignments: np.ndarray      # [n_vectors] int32 centroid id per vector
    codes: np.ndarray            # [n_vectors, W] packed residual words
    vec2doc: np.ndarray          # [n_vectors] int64 doc id
    doc_offsets: np.ndarray      # [n_docs + 1] int64 into vector arrays
    doc_maxlen: int
    recon: Optional[DocStore] = None   # decoded-vector cache, lazy
    _packed_padded: Optional[Tuple] = field(default=None, repr=False)
    _device_ivf: Optional[DeviceInvertedLists] = field(default=None,
                                                       repr=False)

    @property
    def n_docs(self) -> int:
        return len(self.doc_offsets) - 1

    @property
    def n_vectors(self) -> int:
        return len(self.vec2doc)

    def nbytes(self) -> int:
        """Resident bytes: ids (4B) + packed codes + IVF/doc offsets —
        PLUS the f32 reconstruction cache whenever it is resident.

        The recon store is re-derivable (not persisted), but a resident
        cache is real memory: hiding it here made the plaid footprint
        look 8-14x smaller than it was. On the packed rerank path it is
        simply never built, so the two numbers agree again.
        """
        total = (self.assignments.nbytes + self.codes.nbytes
                 + self.ivf.ids.nbytes + self.ivf.offsets.nbytes
                 + self.vec2doc.nbytes + self.doc_offsets.nbytes
                 + np.asarray(self.codec.centroids).nbytes)
        if self.recon is not None:
            total += self.recon.nbytes(bytes_per_dim=4, live_only=False)
        return total

    def _padded_len(self) -> int:
        """Tight padded width L = min(doc_maxlen, longest doc)."""
        lens = np.diff(self.doc_offsets)
        return int(min(self.doc_maxlen, max(lens.max(initial=0), 1)))

    def device_bytes_detail(self) -> dict:
        """Device-resident bytes of the query-time doc representation.

        ``packed``: the [n, L] centroid ids (4B) + [n, L, W] residual
        words (4B each) + [n, L] mask (1B) the compressed-domain rerank
        streams. ``codec``: centroid/cutoff/value tables. ``recon``: the
        decoded f32 view, counted only while resident — 0 under packed
        serving, which never builds it.
        """
        n = max(self.n_docs, 1)
        L = self._padded_len()
        W = self.codes.shape[1]
        return {
            "packed": n * L * (4 + 4 * W + 1),
            "codec": (np.asarray(self.codec.centroids).nbytes
                      + np.asarray(self.codec.cutoffs).nbytes
                      + np.asarray(self.codec.values).nbytes),
            "recon": (self.recon.device_nbytes()
                      if self.recon is not None else 0),
            # device IVF (candidate-generation tables), lazy like recon
            "ivf": (self._device_ivf.device_bytes()
                    if self._device_ivf is not None else 0),
        }

    def device_bytes(self) -> int:
        return sum(self.device_bytes_detail().values())

    # --------------------------------------------------------- cached views
    def _decode_docs(self, assignments, codes, lens):
        """Decode per-doc vector lists from flat code rows."""
        if len(assignments) == 0:
            return [np.zeros((0, self.codec.dim), np.float32)
                    for _ in range(len(lens))]
        rec = np.asarray(decode(self.codec, jnp.asarray(assignments),
                                jnp.asarray(codes)))
        bounds = np.zeros(len(lens) + 1, np.int64)
        np.cumsum(lens, out=bounds[1:])
        return [rec[bounds[i]:bounds[i + 1]] for i in range(len(lens))]

    def recon_store(self) -> DocStore:
        """f32 reconstruction cache, built ON FIRST USE only.

        The packed rerank path never calls this; it exists for the
        corpus-wide dense scoring path (tiny corpora, where a resident
        decoded view beats per-query decode) and for debugging.
        """
        if self.recon is None:
            self.recon = DocStore(self.codec.dim, self.doc_maxlen)
            self.recon.add(self._decode_docs(self.assignments, self.codes,
                                             np.diff(self.doc_offsets)))
        return self.recon

    def padded_packed(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Cached device view of the packed doc representation:
        (ids [n, L] int32, words [n, L, W] uint32, mask [n, L]) with L
        the tight width min(doc_maxlen, longest doc). This — not an f32
        rebuild — is what stage 3 and the compressed-domain stage 4
        gather from.
        """
        if self._packed_padded is None:
            n, W = self.n_docs, self.codes.shape[1]
            L = self._padded_len()
            ids = np.zeros((max(n, 1), L), np.int32)
            words = np.zeros((max(n, 1), L, W), self.codes.dtype)
            mask = np.zeros((max(n, 1), L), bool)
            if n and self.n_vectors:
                lens = np.diff(self.doc_offsets)
                kept = np.minimum(lens, L)
                rows = np.repeat(np.arange(n), kept)
                cols = ragged_arange(kept)
                src = np.repeat(self.doc_offsets[:-1], kept) + cols
                ids[rows, cols] = self.assignments[src]
                words[rows, cols] = self.codes[src]
                mask[rows, cols] = True
            self._packed_padded = (jnp.asarray(ids), jnp.asarray(words),
                                   jnp.asarray(mask))
        return self._packed_padded

    def padded_codes(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Centroid-id view + mask for stage-3 approx scoring — a slice
        of the packed view (masked slots read id 0 and are zeroed by the
        mask downstream)."""
        ids, _, mask = self.padded_packed()
        return ids, mask

    def device_ivf(self, list_cap: int = 0) -> DeviceInvertedLists:
        """Cached device IVF layout (CSR + padded unique-doc lists),
        shipped once per mutation epoch. The exact build (``list_cap=0``,
        ``overflow == 0``) is what the device candidate path gathers
        from; explicit caps bypass the cache (footprint experiments)."""
        if list_cap:
            return build_device_inverted_lists(self.ivf, self.vec2doc,
                                               self.n_docs, list_cap)
        if self._device_ivf is None:
            self._device_ivf = build_device_inverted_lists(
                self.ivf, self.vec2doc, self.n_docs)
        return self._device_ivf

    def _invalidate(self):
        self._packed_padded = None
        self._device_ivf = None

    # ------------------------------------------------------------------ CRUD
    def add(self, doc_vectors: list) -> np.ndarray:
        """Append documents (list of [n_i, dim] arrays). Returns new doc ids."""
        new_ids = np.arange(self.n_docs, self.n_docs + len(doc_vectors))
        if len(doc_vectors) == 0:
            return new_ids
        dim = self.codec.dim
        flat = np.concatenate(
            [np.asarray(v, np.float32).reshape(-1, dim)
             for v in doc_vectors])
        lens = np.array([len(v) for v in doc_vectors], np.int64)
        if len(flat):
            a, w = encode(self.codec, jnp.asarray(flat))
            a, w = np.asarray(a), np.asarray(w)
        else:
            a = np.zeros((0,), self.assignments.dtype)
            w = np.zeros((0, self.codes.shape[1]), self.codes.dtype)
        if self.recon is not None:      # keep the cache coherent if built
            self.recon.add(self._decode_docs(a, w, lens))
        self.assignments = np.concatenate([self.assignments, a])
        self.codes = np.concatenate([self.codes, w])
        self.vec2doc = np.concatenate(
            [self.vec2doc, np.repeat(new_ids, lens)])
        self.doc_offsets = np.concatenate(
            [self.doc_offsets, self.doc_offsets[-1] + np.cumsum(lens)])
        self.ivf = build_inverted_lists(self.assignments,
                                        self.codec.n_centroids)
        self._invalidate()
        return new_ids

    def delete(self, doc_ids) -> None:
        """Remove documents (compacting rebuild of the flat arrays)."""
        keep = ~np.isin(self.vec2doc, np.asarray(doc_ids))
        lens = np.diff(self.doc_offsets)
        doc_keep = ~np.isin(np.arange(self.n_docs), np.asarray(doc_ids))
        self.assignments = self.assignments[keep]
        self.codes = self.codes[keep]
        new_lens = lens[doc_keep]
        self.doc_offsets = np.zeros(len(new_lens) + 1, np.int64)
        np.cumsum(new_lens, out=self.doc_offsets[1:])
        self.vec2doc = np.repeat(np.arange(len(new_lens)), new_lens)
        self.ivf = build_inverted_lists(self.assignments,
                                        self.codec.n_centroids)
        self.recon = None            # rebuilt lazily from compacted codes
        self._invalidate()


def build_plaid_index(doc_vectors: list, codec: ResidualCodec,
                      doc_maxlen: int = 256) -> PLAIDIndex:
    """doc_vectors: list of [n_i, dim] float arrays (already pooled)."""
    lens = np.array([len(v) for v in doc_vectors], np.int64)
    flat = (np.concatenate([np.asarray(v, np.float32).reshape(-1, codec.dim)
                            for v in doc_vectors])
            if len(doc_vectors) else np.zeros((0, codec.dim), np.float32))
    if len(flat):
        a, w = encode(codec, jnp.asarray(flat))
        a, w = np.asarray(a), np.asarray(w)
    else:
        a = np.zeros((0,), np.int32)
        w = np.zeros((0, max(codec.dim * codec.bits // 32, 1)), np.uint32)
    doc_offsets = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=doc_offsets[1:])
    return PLAIDIndex(
        codec=codec,
        ivf=build_inverted_lists(a, codec.n_centroids),
        assignments=a,
        codes=w,
        vec2doc=np.repeat(np.arange(len(lens)), lens),
        doc_offsets=doc_offsets,
        doc_maxlen=doc_maxlen,
    )


# ---------------------------------------------------------------------------
# Batched search stages
# ---------------------------------------------------------------------------
def _pad_up(n: int, mult: int) -> int:
    return max(((n + mult - 1) // mult) * mult, mult)


@jax.jit
def _centroid_scores_batch(qs, centroids):
    """Stage 1: qs [Nq, Lq, dim] -> centroid scores [Nq, Lq, K]."""
    return jnp.einsum("qld,kd->qlk", qs.astype(jnp.float32),
                      centroids.astype(jnp.float32))


def _gather_candidates(index: PLAIDIndex, probe: np.ndarray,
                       live: Optional[np.ndarray] = None,
                       probe_valid: Optional[np.ndarray] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Stage 2: probe [Nq, Lq, nprobe] centroid ids -> padded candidate
    doc ids [Nq, C] + validity mask [Nq, C]. Fully vectorized.
    ``probe_valid`` (same shape as ``probe``) drops masked-token probes:
    top_k over an all--inf row returns centroids 0..nprobe-1, and
    walking those lists would silently inflate the candidate sets."""
    Nq = probe.shape[0]
    K = index.ivf.n_centroids
    flat = probe.reshape(Nq, -1).astype(np.int64)
    keys = np.arange(Nq)[:, None] * K + flat
    if probe_valid is not None:
        keys = keys[probe_valid.reshape(Nq, -1)]
    # dedupe (query, centroid) pairs so each probed list is walked once
    qc = np.unique(keys)
    qi, ci = qc // K, qc % K
    starts = index.ivf.offsets[ci]
    lens = index.ivf.offsets[ci + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return (np.zeros((Nq, 1), np.int64), np.zeros((Nq, 1), bool))
    # flat positions into ivf.ids for every (pair, member) without a loop
    pos = np.repeat(starts, lens) + ragged_arange(lens)
    docs = index.vec2doc[index.ivf.ids[pos]]
    qidx = np.repeat(qi, lens)
    # dedupe (query, doc) pairs -> per-query candidate sets
    qd = np.unique(qidx * np.int64(index.n_docs) + docs)
    qidx, docs = qd // index.n_docs, qd % index.n_docs
    if live is not None:
        keep = live[docs]
        qidx, docs = qidx[keep], docs[keep]
    return pad_candidate_sets(qidx, docs, Nq, block=_CAND_BLOCK)


@functools.partial(jax.jit, static_argnames=("block",))
def _approx_scores_batch(cs, codes, code_mask, cand_mask, t_cs,
                         block: int = _CAND_BLOCK):
    """Stage 3: centroid-only MaxSim for every (query, candidate) pair.

    cs: [Nq, Lq, K]; codes/code_mask: [Nq, C, L] per-candidate centroid
    ids; cand_mask: [Nq, C]. Scanned over candidate blocks to bound the
    [Nq, block, L, Lq] gather. Returns approx scores [Nq, C] (-inf on
    padded candidate slots).
    """
    Nq, C, L = codes.shape
    cs_p = jnp.where(cs >= t_cs, cs, 0.0)              # [Nq, Lq, K]
    csT = jnp.swapaxes(cs_p, 1, 2)                     # [Nq, K, Lq]
    nb = C // block
    codes_b = jnp.moveaxis(codes.reshape(Nq, nb, block, L), 1, 0)
    mask_b = jnp.moveaxis(code_mask.reshape(Nq, nb, block, L), 1, 0)

    def one(carry, args):
        cb, mb = args                                  # [Nq, block, L]
        vals = jax.vmap(lambda t, i: t[i])(csT, cb)    # [Nq, block, L, Lq]
        vals = jnp.where(mb[..., None], vals, 0.0)
        # the query tokens summed in the bag kernel's order
        return carry, fold_sum(vals.max(axis=2), 2)[..., 0]  # [Nq, block]

    _, out = jax.lax.scan(one, 0, (codes_b, mask_b))   # [nb, Nq, block]
    approx = jnp.moveaxis(out, 0, 1).reshape(Nq, C)
    return jnp.where(cand_mask, approx, -jnp.inf)


def _ladder(n: int) -> int:
    """The ``pad_candidate_sets`` geometric width for a max count of n."""
    n = max(int(n), 1)
    return _CAND_BLOCK << max(int(np.ceil(np.log2(-(-n // _CAND_BLOCK)))), 0)


def _floor_ladder(n: int) -> int:
    """Largest geometric width <= n (0 if n < the smallest width)."""
    if n < _CAND_BLOCK:
        return 0
    C = _CAND_BLOCK
    while C * 2 <= n:
        C *= 2
    return C


def device_probe_plan(index: PLAIDIndex, Lq: int, nprobe: int,
                      ndocs: int, probe_kernel: str = "auto", *,
                      t_cs: float):
    """Static decision + geometry for the device-resident candidate path.

    Returns ``(True, (div, k, c_score, s_out))``, or ``(False, reason)``
    where ``reason`` names why the host path serves: ``host_kernel``
    (pinned), ``empty``, ``negative_t_cs``, ``long_docs``, ``overflow``,
    ``dense`` or ``gather_cap`` (one for each condition below). The
    device path engages only when it is PROVABLY bitwise-equal to the
    host path:

      * its stage 3 scores every doc from its centroid bag
        (``kernels/plaid_probe``), which equals the host's score over
        the doc's code row only when pruned scores are >= 0
        (``t_cs >= 0``: an absent centroid's 0 must never win) and no
        doc is longer than ``doc_maxlen`` (the code row cuts such a doc,
        its bag does not);
      * the device IVF view is exact (``overflow == 0``);
      * the dense corpus-wide dispatch is statically unreachable — for
        every possible per-query candidate count, the host path's final
        padded width stays below ``n_docs`` (otherwise the host would
        switch to the corpus-scan rerank, a different program whose
        dispatch depends on runtime counts the device path cannot see
        without a host sync);
      * in "auto" mode, the padded gather stays under a memory cap.

    ``c_score`` is the static stage-2/3 width (every possible candidate
    fits), ``s_out`` the static output width (= the rerank slate width).
    """
    assert probe_kernel in PROBE_KERNELS, probe_kernel
    if probe_kernel == "host":
        return False, "host_kernel"
    if index.n_vectors == 0 or index.n_docs == 0:
        return False, "empty"
    if t_cs < 0:
        return False, "negative_t_cs"
    if np.diff(index.doc_offsets).max() > index.doc_maxlen:
        return False, "long_docs"
    div = index.device_ivf()
    if div.overflow != 0:
        return False, "overflow"
    n_docs = index.n_docs
    k = min(nprobe, index.codec.n_centroids)
    W = max(Lq, 1) * k * div.list_cap       # padded gather slots / query
    c_score = _pad_up(min(W, n_docs), _CAND_BLOCK)
    s_out = min(c_score, _pad_up(int(ndocs), _CAND_BLOCK))
    # worst-case host output width over all data: the widest no-prune
    # gather (largest ladder value <= ndocs, capped by the gather bound)
    # vs the pruned width (ndocs block-padded, reachable only when the
    # gather ladder can exceed ndocs)
    lmax = _ladder(min(W, n_docs))
    f_prune = _pad_up(int(ndocs), _CAND_BLOCK) if lmax > ndocs else 0
    f_noprune = min(lmax, _floor_ladder(int(ndocs)))
    if max(f_prune, f_noprune) >= n_docs:
        return False, "dense"
    if (probe_kernel != "device"
            and div.doc_member.size > _DEVICE_GATHER_CAP):
        return False, "gather_cap"
    return True, (div, k, c_score, s_out)


@functools.partial(jax.jit, static_argnames=("k", "t_cs", "ndocs",
                                             "c_score", "s_out", "impl"))
def _device_candidates(cs, qm, doc_member, live, *, k: int, t_cs: float,
                       ndocs: int, c_score: int, s_out: int, impl: str):
    """Stages 1-3 as one device program — no host round-trip.

    Bitwise contract (pinned by tests/test_plaid_probe.py): candidate
    ids, validity, and slot order equal the host path's —

      * probe: same ``lax.top_k`` over the same (masked) centroid
        scores; masked-token probes dropped (the host bugfix twin);
      * gather/dedupe: probed-centroid one-hot rows x the 0/1
        ``doc_member`` table (one matmul; counts are small integers,
        exact in f32) -> per-query doc membership -> cumsum compaction.
        Ascending unique doc ids land in slots 0..count-1, exactly
        ``np.unique`` + ``pad_candidate_sets`` (no device sort or big
        scatter — the two primitives XLA serializes on every backend);
      * prune: the host's data-dependent decision (padded gather width
        > ndocs) is replicated on device from the counts and the same
        geometric ladder, then taken as a ``lax.cond`` — both branches
        emit the static width ``s_out``, so one executable serves the
        whole stream (no-retrace contract).

      * score: every doc from its centroid bag (the ``doc_member``
        rows), by ``kernels/plaid_probe`` (``impl``: its dispatcher's
        ``"kernel"`` or ``"ref"``); exact where ``device_probe_plan``
        engages this path.
    """
    Nq = cs.shape[0]
    n_docs = live.shape[0]
    # named scopes only label the ops' metadata (device trace)
    with jax.named_scope("candidates/probe"):
        csm = jnp.where(qm[:, :, None], cs, -jnp.inf)
        _, probe = jax.lax.top_k(csm, k)                 # [Nq, Lq, k]
        flat = probe.reshape(Nq, -1)                     # [Nq, Lq*k]
        pvalid = jnp.broadcast_to(qm[:, :, None], probe.shape
                                  ).reshape(Nq, -1)
    with jax.named_scope("candidates/gather"):
        # (query, doc) set union as ONE matmul: a probed-centroid one-hot
        # row per query times the 0/1 membership table counts, exactly
        # (small integers in f32), how many probed lists own each doc
        K = doc_member.shape[0]
        probed = jnp.any(
            (flat[:, :, None] == jax.lax.broadcasted_iota(jnp.int32,
                                                          (1, 1, K), 2))
            & pvalid[:, :, None], axis=1)                # [Nq, K]
        hits = jax.lax.dot_general(
            probed.astype(jnp.float32), doc_member,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Nq, n_docs]
        member = (hits > 0.0) & live[None, :]
        counts = member.sum(axis=1).astype(jnp.int32)    # [Nq]
        # compact member columns ascending via cumsum positions —
        # bit-for-bit np.unique's ascending unique ids at slots 0..cnt-1
        pos = jnp.cumsum(member, axis=1, dtype=jnp.int32) - 1
        docid = jax.lax.broadcasted_iota(jnp.int32, (Nq, n_docs), 1)
        tpos = jnp.where(member, pos, jnp.int32(c_score))  # cnt <= c_score
        cand_c = jax.vmap(lambda t, d: jnp.zeros((c_score,), jnp.int32)
                          .at[t].set(d, mode="drop"))(tpos, docid)
        mask_c = (jax.lax.broadcasted_iota(jnp.int32, (Nq, c_score), 1)
                  < counts[:, None])   # pad slots read doc 0, as on host

    with jax.named_scope("candidates/prune"):
        # the host prune decision, replicated: padded gather width > ndocs
        maxc = jnp.maximum(counts.max(), 1)
        ladder = jnp.asarray([_CAND_BLOCK << m for m in range(26)],
                             jnp.int32)
        host_c = jnp.min(jnp.where(ladder >= maxc, ladder,
                                   jnp.int32(2**31 - 1)))
        keep = min(ndocs, c_score)

        def unpruned(cand_c, mask_c):
            return cand_c[:, :s_out], mask_c[:, :s_out]

        def pruned(cand_c, mask_c):
            from repro.kernels.plaid_probe.ops import plaid_probe_bag_scores
            csp = jnp.where(csm >= t_cs, csm, 0.0)       # masked -> 0
            bag = plaid_probe_bag_scores(csp, doc_member,
                                         impl=impl)      # [Nq, n_docs]
            # the candidates are the member docs, ascending in slots
            # 0..count-1, so a top_k over doc ids picks the docs the
            # slot top_k picks, in its order (ties go to the lower index
            # either way), with no gather into the slots (keep <= n_docs:
            # the plan's dense rule)
            approx = jnp.where(member, bag, -jnp.inf)
            top_s, top_i = jax.lax.top_k(approx, keep)
            mask_p = jnp.isfinite(top_s)
            cand_p = jnp.where(mask_p, top_i, 0)     # pads read doc 0
            if keep < s_out:
                cand_p = jnp.pad(cand_p, ((0, 0), (0, s_out - keep)))
                mask_p = jnp.pad(mask_p, ((0, 0), (0, s_out - keep)))
            return cand_p, mask_p

        return jax.lax.cond(host_c > ndocs, pruned, unpruned, cand_c,
                            mask_c)


def plaid_candidates(index: PLAIDIndex, qs: np.ndarray,
                     nprobe: int = 8, t_cs: float = 0.3,
                     ndocs: int = 8192,
                     live: Optional[np.ndarray] = None,
                     q_mask: Optional[np.ndarray] = None,
                     probe_kernel: str = "auto"
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Stages 1-3 for a query batch: qs [Nq, Lq, dim] -> survivor doc
    ids [Nq, S] + validity mask [Nq, S] (S <= ndocs block-padded).
    Masked query tokens contribute nothing to probes or approx scores.

    ``probe_kernel`` picks the stage-2/3 implementation (RUNTIME-ONLY,
    never persisted): "host" is the vectorized-numpy reference path
    (host arrays out); "device"/"auto" run the device-resident pipeline
    (device arrays out, zero host hops) whenever ``device_probe_plan``
    proves it bitwise-safe, falling back to the host path otherwise.
    """
    qs = np.asarray(qs, np.float32)
    Nq = len(qs)
    if index.n_vectors == 0:
        return np.zeros((Nq, 1), np.int64), np.zeros((Nq, 1), bool)
    use_device, geom = device_probe_plan(index, qs.shape[1], nprobe,
                                         ndocs, probe_kernel, t_cs=t_cs)
    args = ({"path": "device", "scorer": "bag"} if use_device
            else {"path": "host", "fallback": geom})
    with obs.span(obs.PLAID_CANDIDATES, **args) as sp:
        out, h2d, d2h = _candidates(index, qs, use_device, geom, nprobe,
                                    t_cs, ndocs, live, q_mask)
        sp.set_metadata(h2d_bytes=h2d, d2h_bytes=d2h)
    return out


def _candidates(index: PLAIDIndex, qs: np.ndarray, use_device: bool, geom,
                nprobe: int, t_cs: float, ndocs: int, live, q_mask):
    """``plaid_candidates`` past its plan: ((cand, mask), bytes copied to
    the device, bytes copied back)."""
    Nq = len(qs)
    centroids = index.codec.centroids
    h2d = obs.host_nbytes(qs, centroids)
    cs = _centroid_scores_batch(jnp.asarray(qs, jnp.float32),
                                jnp.asarray(centroids))
    if use_device:
        div, k, c_score, s_out = geom
        qm = (jnp.ones((Nq, qs.shape[1]), bool) if q_mask is None
              else np.asarray(q_mask, bool))
        live_dev = (jnp.ones(index.n_docs, bool) if live is None
                    else (live if isinstance(live, jax.Array)
                          else np.asarray(live, bool)))
        h2d += obs.host_nbytes(qm, live_dev)
        return _device_candidates(
            cs, jnp.asarray(qm), div.doc_member, jnp.asarray(live_dev),
            k=k, t_cs=float(t_cs), ndocs=int(ndocs), c_score=c_score,
            s_out=s_out, impl="kernel" if _on_tpu() else "ref"), h2d, 0
    if q_mask is not None:
        # masked tokens: -inf centroid scores are pruned to 0 in stage 3,
        # and their (degenerate) probe picks are dropped before the
        # gather — top_k over an all--inf row would otherwise walk
        # centroids 0..nprobe-1's lists into the candidate set
        h2d += obs.host_nbytes(q_mask)
        cs = jnp.where(jnp.asarray(q_mask, bool)[:, :, None], cs, -jnp.inf)
    k = min(nprobe, index.codec.n_centroids)
    _, probe = jax.lax.top_k(cs, k)                    # [Nq, Lq, nprobe]
    probe = np.asarray(probe)
    d2h = probe.nbytes
    probe_valid = (None if q_mask is None else np.broadcast_to(
        np.asarray(q_mask, bool)[:, :, None], (Nq, qs.shape[1], k)))
    cand, cmask = _gather_candidates(index, probe, live, probe_valid)
    if cand.shape[1] <= ndocs:
        return (cand, cmask), h2d, d2h
    codes, tok_mask = index.padded_codes()
    idx = jnp.asarray(cand)
    h2d += cand.nbytes + 2 * cmask.nbytes
    approx = _approx_scores_batch(
        cs, jnp.take(codes, idx, axis=0),
        jnp.take(tok_mask, idx, axis=0) & jnp.asarray(cmask)[:, :, None],
        jnp.asarray(cmask), t_cs)
    keep = min(ndocs, cand.shape[1])           # honor the ndocs budget
    top_s, top_i = jax.lax.top_k(approx, keep)
    top_i = np.asarray(top_i)
    cand = np.take_along_axis(cand, top_i, axis=1)
    cmask = np.asarray(jnp.isfinite(top_s))
    d2h += top_i.nbytes + cmask.nbytes
    S = _pad_up(keep, _CAND_BLOCK)             # block-pad for jit reuse
    if S > keep:
        cand = np.pad(cand, ((0, 0), (0, S - keep)))
        cmask = np.pad(cmask, ((0, 0), (0, S - keep)))
    return (cand, cmask), h2d, d2h


def _decode_rows(codec: ResidualCodec, ids, words):
    """Decode gathered padded rows: ids [..., Ld], words [..., Ld, W]
    -> [..., Ld, dim] f32. Row-for-row ``quantization.decode``, so the
    result is bitwise what the reconstruction DocStore would hold."""
    shape = ids.shape
    v = decode(codec, ids.reshape(-1), words.reshape(-1, words.shape[-1]))
    return v.reshape(*shape, codec.dim)


def maxsim_packed_rerank_store(index: PLAIDIndex, q, q_mask, cand,
                               cand_mask, *, slab: int = 1024):
    """Compressed-domain stage 4: gather PACKED rows for the survivors
    and score them, never materializing an f32 reconstruction store.

    Slabbed over the candidate axis like ``maxsim_rerank_store`` (same
    slab width, same -inf/mask epilogue, so candidate padding and tie
    order are identical). On TPU the fused kernel unpacks+reconstructs
    in VMEM; off-TPU the gathered rows are decoded eagerly through
    ``quantization.decode`` — op for op the recon path's decode — and
    fed to the same ``maxsim_rerank`` dispatcher, making the scores
    bitwise-equal to the reconstruction path.
    cand/cand_mask: [Nq, C] host arrays -> scores [Nq, C] (-inf invalid).
    ``q`` and ``q_mask`` may be host arrays; ``q_mask=None`` means every
    query token scores.
    """
    with obs.span(obs.PLAID_RERANK) as sp:
        codec = index.codec
        ids, words, tmask = index.padded_packed()
        h2d = obs.host_nbytes(q, q_mask)
        q = jnp.asarray(q, jnp.float32)
        q_mask = (jnp.ones(q.shape[:2], bool) if q_mask is None
                  else jnp.asarray(q_mask))
        if not isinstance(cand, jax.Array):
            cand = np.asarray(cand, np.int64)
            cand_mask = np.asarray(cand_mask)
        parts = []
        for lo in range(0, cand.shape[1], slab):
            c, cm = cand[:, lo:lo + slab], cand_mask[:, lo:lo + slab]
            h2d += obs.host_nbytes(c, cm)
            c, cm = jnp.asarray(c), jnp.asarray(cm)
            aw = jnp.take(ids, c, axis=0)                  # [Nq, S, Ld]
            ww = jnp.take(words, c, axis=0)                # [Nq, S, Ld, W]
            dm = jnp.take(tmask, c, axis=0) & cm[:, :, None]
            if _on_tpu():
                from repro.kernels.maxsim_packed.ops import (
                    maxsim_packed_rerank)
                s = maxsim_packed_rerank(q, q_mask, ww, aw, dm,
                                         codec.centroids, codec.values,
                                         bits=codec.bits)
            else:
                s = maxsim_rerank(q, q_mask, _decode_rows(codec, aw, ww),
                                  dm)
            parts.append(jnp.where(cm, s, -jnp.inf))
        sp.set_metadata(h2d_bytes=h2d)
        return (parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=1))


def plaid_search_batch(index: PLAIDIndex, qs: np.ndarray, k: int = 10,
                       nprobe: int = 8, t_cs: float = 0.3,
                       ndocs: int = 8192, probe_kernel: str = "auto"
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """True batch API: qs [Nq, Lq, dim] -> (scores [Nq, k], ids [Nq, k];
    -inf/-1 pads). One traced rerank for the whole batch."""
    qs = np.asarray(qs, np.float32)
    Nq = len(qs)
    cand, cmask = plaid_candidates(index, qs, nprobe=nprobe, t_cs=t_cs,
                                   ndocs=ndocs, probe_kernel=probe_kernel)
    # the empty-batch early exit is a host decision; keep device
    # candidates on device (rerank's -inf epilogue handles all-invalid)
    if not isinstance(cmask, jax.Array) and not cmask.any():
        return (np.full((Nq, k), -np.inf, np.float32),
                np.full((Nq, k), -1, np.int64))
    qm = jnp.ones(qs.shape[:2], bool)
    scores = maxsim_packed_rerank_store(index, qs, qm, cand, cmask)
    return topk_dispatch(scores, cand, k).fetch()


def plaid_search(index: PLAIDIndex, q: np.ndarray, k: int = 10,
                 nprobe: int = 8, t_cs: float = 0.3,
                 ndocs: int = 8192, probe_kernel: str = "auto"
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """One query: q [Lq, dim] -> (scores [<=k], doc ids [<=k]) best-first."""
    S, I = plaid_search_batch(index, np.asarray(q, np.float32)[None], k=k,
                              nprobe=nprobe, t_cs=t_cs, ndocs=ndocs,
                              probe_kernel=probe_kernel)
    valid = I[0] >= 0
    return S[0][valid], I[0][valid]
