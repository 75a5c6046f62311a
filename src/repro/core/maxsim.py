"""MaxSim late-interaction scoring (ColBERT):  S(q, D) = sum_i max_j q_i . d_j.

The query-time hot path the whole index feeds. jnp reference here; the
Pallas kernel (kernels/maxsim) implements the same contraction with doc-token
blocks streamed through VMEM and a running max (dispatched via
``kernels.maxsim.ops.maxsim`` when on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.sharding.api import constrain


def maxsim(q, q_mask, d, d_mask):
    """q: [Lq, dim]; d: [Ld, dim] -> scalar score."""
    sim = q @ d.T                                      # [Lq, Ld]
    sim = jnp.where(d_mask[None, :], sim, -jnp.inf)
    best = jnp.max(sim, axis=-1)
    best = jnp.where(q_mask & jnp.isfinite(best), best, 0.0)
    return jnp.sum(best)


@jax.jit
def maxsim_scores(q, q_mask, d, d_mask):
    """Score every query against every doc.

    q: [Nq, Lq, dim]; q_mask: [Nq, Lq]; d: [Nd, Ld, dim]; d_mask: [Nd, Ld]
    -> scores [Nq, Nd] float32.
    """
    q = constrain(q.astype(jnp.float32), "queries", None, None)
    d = constrain(d.astype(jnp.float32), "docs", None, None)
    sim = jnp.einsum("qld,nkd->qnlk", q, d)            # [Nq, Nd, Lq, Ld]
    sim = jnp.where(d_mask[None, :, None, :], sim, -jnp.inf)
    best = jnp.max(sim, axis=-1)                       # [Nq, Nd, Lq]
    best = jnp.where(q_mask[:, None, :] & jnp.isfinite(best), best, 0.0)
    return jnp.sum(best, axis=-1)                      # [Nq, Nd]


@functools.partial(jax.jit, static_argnames=("block", "unroll"))
def maxsim_scores_blocked(q, q_mask, d, d_mask, block: int = 256,
                          unroll: bool = False):
    """Memory-bounded variant: docs processed in blocks via lax.scan.

    Needed when Nd * Lq * Ld would blow HBM; the Pallas kernel is the fused
    version of exactly this loop. ``unroll`` is the roofline-analysis mode
    (cost_analysis counts loop bodies once).
    """
    Nd = d.shape[0]
    assert Nd % block == 0, (Nd, block)
    nb = Nd // block
    db = d.reshape(nb, block, *d.shape[1:])
    mb = d_mask.reshape(nb, block, d_mask.shape[-1])

    def one(carry, args):
        dd, mm = args
        return carry, maxsim_scores(q, q_mask, dd, mm)   # [Nq, block]

    _, out = jax.lax.scan(one, 0, (db, mb),
                          unroll=nb if unroll else 1)    # [nb, Nq, block]
    return jnp.swapaxes(out, 0, 1).reshape(q.shape[0], Nd)


def topk_docs(scores, k):
    """scores [Nq, Nd] -> (top scores [Nq,k], doc ids [Nq,k])."""
    return jax.lax.top_k(scores, k)


# ---------------------------------------------------------------------------
# Engine entry points: Pallas kernel on TPU, jnp reference elsewhere
# (interpret-mode Pallas on CPU is correctness-only; the jnp path keeps the
#  batched engine fast on hosts while tracing to the same shapes).
# ---------------------------------------------------------------------------
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# jit once at import: the kernel ref oracle IS the CPU rerank path
from repro.kernels.maxsim.ref import maxsim_rerank_ref as _rerank_ref
_rerank_jnp = jax.jit(_rerank_ref)


_ALL_DOCS_BLOCK = 2048     # above this, block the corpus scan (HBM bound)


def maxsim_all_docs(q, q_mask, d, d_mask):
    """All-pairs scores [Nq, Nd] — flat search / shared-corpus stage.

    Large corpora go through the lax.scan-blocked variant so the
    [Nq, Nd, Lq, Ld] similarity intermediate never materializes whole.
    """
    if _on_tpu():
        from repro.kernels.maxsim.ops import maxsim as maxsim_kernel
        return maxsim_kernel(q, q_mask, d, d_mask)
    Nd = d.shape[0]
    if Nd <= _ALL_DOCS_BLOCK:
        return maxsim_scores(q, q_mask, d, d_mask)
    pad = (-Nd) % _ALL_DOCS_BLOCK
    if pad:
        d = jnp.pad(d, ((0, pad), (0, 0), (0, 0)))
        d_mask = jnp.pad(d_mask, ((0, pad), (0, 0)))
    out = maxsim_scores_blocked(q, q_mask, d, d_mask,
                                block=_ALL_DOCS_BLOCK)
    return out[:, :Nd]


class PendingTopK:
    """A batch's top-k as dispatched: the [Nq, kk] scores and the ids
    (or, for host candidates, the winning columns) may still be in
    flight on the device.

    ``fetch()`` makes the one device-to-host copy and the host epilogue
    (host-candidate gather, -inf slots to id -1, pads to k); later calls
    return the same arrays. Unpacking a pending result as ``(scores,
    ids)`` fetches it, so a caller that unpacks ``search_batch``'s result
    reads the same either way.
    """

    def __init__(self, top_s, ids, cand, k: int):
        self._dev = (top_s, ids)
        self._cand = cand               # host ids to gather from, or None
        self._k = k
        self._out = None

    def fetch(self):
        import numpy as np
        if self._out is None:
            with obs.span(obs.PLAID_TOPK) as sp:
                top_s, ids = jax.device_get(self._dev)
                sp.set_metadata(d2h_bytes=top_s.nbytes + ids.nbytes)
                ids = (ids.astype(np.int64) if self._cand is None
                       else np.take_along_axis(
                           np.asarray(self._cand, np.int64), ids, axis=1))
            self._dev = None            # let the device buffers go
            ids = np.where(np.isfinite(top_s), ids, -1)
            pad = self._k - top_s.shape[1]
            if pad > 0:
                top_s = np.pad(top_s, ((0, 0), (0, pad)),
                               constant_values=-np.inf)
                ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
            self._out = top_s.astype(np.float32), ids.astype(np.int64)
        return self._out

    def __iter__(self):
        return iter(self.fetch())


def topk_dispatch(scores, cand, k: int) -> PendingTopK:
    """The shared top-k epilogue of every batched search API, device
    half: dispatches the top-k and, for device candidates, the gather of
    the winning ids, and waits for neither.

    scores: [Nq, C] (-inf marks invalid slots); cand: [Nq, C] doc ids
    (device or host) or None when scores are corpus-wide (ids = column
    index). ``fetch()`` on the result gives (scores [Nq, k] f32, ids
    [Nq, k] i64) padded with -inf/-1.
    """
    top_s, top_i = jax.lax.top_k(scores, min(k, scores.shape[1]))
    if isinstance(cand, jax.Array):
        # device candidates: gather the winning ids on device so the
        # ONLY host transfer after encode is this [Nq, k] result
        return PendingTopK(top_s, jnp.take_along_axis(cand, top_i, axis=1),
                           None, k)
    return PendingTopK(top_s, top_i, cand, k)


def topk_shard(scores, cand, k: int, base: int = 0):
    """Device-side per-shard top-k: the unit a sharded/replicated merge
    keeps ON DEVICE so full-width slates never cross the host boundary.

    scores: [Nq, C] (-inf marks invalid slots, device array); cand:
    [Nq, C] local doc ids (host or device) or None when scores are
    corpus-wide (ids = column index). Returns (top scores [Nq, kk] f32,
    GLOBAL ids [Nq, kk] i32) with kk = min(k, C), both device-resident
    on ``scores``' device. Ids are ``cand``-gathered (or the column
    index) shifted by ``base``; slots whose score is -inf carry a
    meaningless id — the final merge epilogue (``topk_dispatch``) maps
    non-finite slots to -1, exactly as the monolithic path does.

    Keeping per-shard top-k is lossless for a global top-k: any shard
    contributes at most k winners, and ``jax.lax.top_k`` orders ties by
    lowest position, so local-top-k-then-merge reproduces the single
    concat-then-top-k bit for bit (scores, ids, AND tie order).

    Ids are i32 on device (x64 is off by default); global doc ids past
    2**31 are out of scope for this layout.
    """
    import numpy as np
    kk = min(k, scores.shape[1])
    top_s, top_i = jax.lax.top_k(scores, kk)
    off = jnp.int32(base)
    if cand is None:
        return top_s, top_i.astype(jnp.int32) + off
    c = (cand.astype(jnp.int32) if isinstance(cand, jax.Array)
         else jnp.asarray(np.asarray(cand, np.int32)))
    return top_s, jnp.take_along_axis(c, top_i, axis=1) + off


def maxsim_rerank(q, q_mask, d, d_mask):
    """Per-query gathered-candidate scores [Nq, S] (one traced batch)."""
    if _on_tpu():
        from repro.kernels.maxsim.ops import maxsim_rerank as rerank_kernel
        return rerank_kernel(q, q_mask, d, d_mask)
    return _rerank_jnp(q, q_mask, d, d_mask)


def maxsim_rerank_store(store, q, q_mask, cand, cand_mask, *,
                        slab: int = 1024):
    """Gather candidates from ``store`` and rerank, slabbed over the
    candidate axis so the [Nq, slab, Ld, dim] gather stays bounded
    (paper-default ndocs=8192 would otherwise materialize tens of GB).
    cand/cand_mask: [Nq, C] host arrays -> scores [Nq, C] (-inf invalid).
    """
    import numpy as np
    q = jnp.asarray(q, jnp.float32)
    parts = []
    for lo in range(0, cand.shape[1], slab):
        c = cand[:, lo:lo + slab]
        cm = jnp.asarray(np.asarray(cand_mask)[:, lo:lo + slab])
        d, dm = store.gather(c)
        s = maxsim_rerank(q, q_mask, d, dm & cm[:, :, None])
        parts.append(jnp.where(cm, s, -jnp.inf))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
