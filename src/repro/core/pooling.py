"""TOKEN POOLING — the paper's contribution (§2), as a drop-in indexing step.

Given per-document token embeddings, group them with one of three clustering
methods and replace each group by its (re-normalized) mean:

  * ``sequential`` — pool runs of ``factor`` consecutive tokens (paper baseline)
  * ``kmeans``     — cosine k-means, K = floor(n/factor) + 1
  * ``ward``       — hierarchical Ward clustering (paper's best method)

No training, no query-time change: this runs between the encoder and the
index. ``pool_factor=1`` or method ``none`` is the identity (the unpooled
baseline every paper table is normalized against).

The ward path dispatches through ``kernels/ward_pool`` (the Pallas
merge-loop kernel on TPU, ``core/ward.py`` elsewhere — bitwise-equal;
``ward_kernel`` pins either), and ``compact_pooled`` compacts ON DEVICE first — a
validity-sort moves the pooled rows doc-major to the front so the
device->host transfer is ``sum(counts)`` rows + a counts vector,
~1/factor of the padded ``[B, N, d]`` tensor
(``compaction_transfer_stats`` reports the measured ratio).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.kmeans import kmeans_cluster_batch
from repro.core.ward import ward_cluster_batch

METHODS = ("none", "sequential", "kmeans", "ward")


def sequential_assign(mask, factor: int):
    """Mask-aware run grouping: the g-th VALID token joins group
    ``g // factor``. mask: [B, N] -> assign [B, N] int32.

    Grouping by valid-token rank (``cumsum(mask) - 1``) rather than raw
    position means punctuation-masked gaps don't split a run: a doc with
    n valid tokens pools to exactly ``ceil(n / factor)`` vectors instead
    of one per partially-covered position block. Masked positions get an
    arbitrary (weight-zero) group id.
    """
    rank = jnp.cumsum(mask.astype(jnp.int32), axis=-1) - 1
    return (jnp.maximum(rank, 0) // factor).astype(jnp.int32)


def _mean_pool_by_assign(x, mask, assign, num_segments: int,
                         renormalize: bool = True):
    """Segment-mean x by assign per document.

    x: [B, N, d]; mask: [B, N]; assign: [B, N] ids in [0, num_segments).
    Returns pooled [B, num_segments, d], pooled_mask [B, num_segments].
    """
    w = mask.astype(jnp.float32)

    def one(xi, wi, ai):
        sums = jax.ops.segment_sum(xi * wi[:, None], ai,
                                   num_segments=num_segments)
        cnts = jax.ops.segment_sum(wi, ai, num_segments=num_segments)
        mean = sums / jnp.maximum(cnts[:, None], 1e-9)
        if renormalize:
            nrm = jnp.linalg.norm(mean, axis=-1, keepdims=True)
            mean = mean / jnp.maximum(nrm, 1e-9)
        return mean * (cnts > 0)[:, None], cnts > 0

    return jax.vmap(one)(x.astype(jnp.float32), w, assign)


@functools.partial(jax.jit, static_argnames=("factor", "method",
                                             "renormalize", "ward_kernel"))
def pool_doc_embeddings(x, mask, factor: int, method: str = "ward",
                        renormalize: bool = True,
                        ward_kernel: str = "auto"
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pool token vectors (the paper's indexing-time compression step).

    Args:
      x: [B, N, d] token embeddings.
      mask: [B, N] bool — True for real tokens.
      factor: the POOLING FACTOR (2 -> 50% fewer vectors, 3 -> 66%, ...).
      method: none | sequential | kmeans | ward.
      ward_kernel: ward implementation — "auto"/"kernel" = the Pallas
        merge-loop kernel (kernels/ward_pool), "ref" = core/ward.py's
        loop. Bitwise-identical outputs either way.

    Returns:
      pooled: [B, N, d] — pooled vectors scattered into slots (zero rows
              where no cluster lives); compact host-side for storage.
      pooled_mask: [B, N] bool — which slots hold a pooled vector.
    """
    assert method in METHODS, method
    B, N, d = x.shape
    if method == "none" or factor <= 1:
        xo = x.astype(jnp.float32)
        if renormalize:
            xo = xo / jnp.maximum(
                jnp.linalg.norm(xo, axis=-1, keepdims=True), 1e-9)
        return jnp.where(mask[..., None], xo, 0.0), mask

    if method == "sequential":
        assign = sequential_assign(mask, factor)
        nseg = (N + factor - 1) // factor
        pooled, pmask = _mean_pool_by_assign(x, mask, assign, nseg,
                                             renormalize)
        pad = N - nseg
        pooled = jnp.pad(pooled, ((0, 0), (0, pad), (0, 0)))
        pmask = jnp.pad(pmask, ((0, 0), (0, pad)))
        return pooled, pmask

    if method == "kmeans":
        assign = kmeans_cluster_batch(x, mask, factor)
        k_max = N // factor + 1
        pooled, pmask = _mean_pool_by_assign(x, mask, assign, k_max,
                                             renormalize)
        pad = N - k_max
        pooled = jnp.pad(pooled, ((0, 0), (0, pad), (0, 0)))
        pmask = jnp.pad(pmask, ((0, 0), (0, pad)))
        return pooled, pmask

    # ward: assign ids live in [0, N) (representative token index)
    if ward_kernel == "ref":
        assign = ward_cluster_batch(x, mask, factor)
    else:
        from repro.kernels.ward_pool.ops import ward_assign
        assign = ward_assign(x, mask, factor, impl=ward_kernel)
    pooled, pmask = _mean_pool_by_assign(x, mask, assign, N, renormalize)
    return pooled, pmask


# device->host compaction traffic, cumulative across compact_pooled
# calls: padded = the [B, N, d] tensor the pre-kernel path shipped,
# compact = what the validity-sorted path actually moves (rows+counts).
_TRANSFER_STATS = {"padded_bytes": 0, "compact_bytes": 0, "batches": 0}


def compaction_transfer_stats(reset: bool = False) -> dict:
    """Cumulative compaction transfer accounting (the bench's
    <= 1/factor + eps gate reads this)."""
    out = dict(_TRANSFER_STATS)
    if reset:
        for k in _TRANSFER_STATS:
            _TRANSFER_STATS[k] = 0
    return out


@jax.jit
def _compact_device(pooled, pooled_mask):
    """Validity-sort pooled slots doc-major-valid-first so the host
    only pulls ``sum(counts)`` rows. The sort key is the flat slot
    index biased by B*N for empty slots — distinct integers, so the
    order is deterministic and equals the boolean-gather order."""
    B, N, d = pooled.shape
    flat_mask = pooled_mask.reshape(-1)
    idx = jnp.arange(B * N, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(flat_mask, idx, idx + B * N))
    flat = pooled.reshape(B * N, d)[order]
    counts = jnp.sum(pooled_mask.astype(jnp.int32), axis=1)
    return flat, counts


def compact_pooled_begin(pooled, pooled_mask):
    """Dispatch the device-side compaction WITHOUT blocking: returns an
    opaque ticket for :func:`compact_pooled_finish`. Lets a caller
    overlap batch i's host fetch with batch i+1's device compute
    (``Indexer.encode_and_pool_counted`` runs a 1-deep pipeline)."""
    flat, counts = _compact_device(pooled, pooled_mask)
    return (flat, counts, pooled.shape, pooled.dtype)


def compact_pooled_finish(ticket):
    """Materialize a :func:`compact_pooled_begin` ticket on the host:
    only ``sum(counts)`` rows + the [B] counts vector cross. Returns
    (per-doc arrays, bytes moved device->host) — the one byte figure
    both the caller's span and ``compaction_transfer_stats`` take."""
    import numpy as np
    flat, counts_dev, shape, dtype = ticket
    counts = np.asarray(counts_dev)
    total = int(counts.sum())
    host = np.asarray(flat[:total])               # the only row transfer
    B, N, d = shape
    moved = host.nbytes + counts.nbytes
    _TRANSFER_STATS["padded_bytes"] += (
        B * N * d * np.dtype(dtype).itemsize)
    _TRANSFER_STATS["compact_bytes"] += moved
    _TRANSFER_STATS["batches"] += 1
    return np.split(host, np.cumsum(counts[:-1])), moved


def compact_pooled(pooled, pooled_mask):
    """Drop empty slots -> list of [n_i, d] numpy arrays.

    Device inputs take the compact-transfer path: slots are sorted by
    validity ON DEVICE and only the ``sum(counts)`` leading rows cross
    to the host (plus the [B] counts vector) — ~1/factor of the padded
    tensor's bytes. Host (numpy) inputs keep the single boolean gather.
    Both paths return bitwise-identical arrays; the per-doc arrays are
    ``np.split`` views on the cumulative counts either way.
    """
    import numpy as np
    if pooled.shape[0] == 0:
        return []
    if isinstance(pooled, jax.Array) and isinstance(pooled_mask,
                                                    jax.Array):
        return compact_pooled_finish(
            compact_pooled_begin(pooled, pooled_mask))[0]
    pooled = np.asarray(pooled)
    pooled_mask = np.asarray(pooled_mask).astype(bool)
    counts = pooled_mask.sum(axis=1)
    flat = pooled[pooled_mask]                    # [sum(counts), d]
    return np.split(flat, np.cumsum(counts[:-1]))


def vector_counts(mask, pooled_mask):
    """(original vector count, pooled vector count) per batch — Table 3."""
    return (int(jnp.sum(mask.astype(jnp.int32))),
            int(jnp.sum(pooled_mask.astype(jnp.int32))))
