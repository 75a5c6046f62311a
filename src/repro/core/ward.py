"""Ward hierarchical clustering (the paper's best pooling method), in JAX.

The paper uses SciPy's agglomerative Ward clustering per document. SciPy's
pointer-chasing NN-chain algorithm is the wrong shape for a TPU; we instead
run the classic greedy Lance–Williams recurrence over a masked distance
matrix with fixed-shape updates:

    state: D2 [N,N] squared Ward linkage distances, sizes [N], active [N],
           assign [N] (token -> surviving cluster representative)
    loop (N-1 times, vmapped over documents):
        (i, j) = argmin over active pairs of D2
        if n_active > K_target:  merge j into i (Lance–Williams update)
        else:                    no-op (fixed trip count across the batch)

Lance–Williams for Ward (squared form, matching scipy.linkage d**2):
    D2(AB, C) = ((sA+sC) D2(A,C) + (sB+sC) D2(B,C) - sC D2(A,B)) / (sA+sB+sC)
Singleton init: D2(i, j) = ||x_i - x_j||^2.

Cosine-vs-Euclidean: the paper clusters on cosine distance; for unit vectors
||a-b||^2 = 2(1-cos), a monotone map, so the merge order is identical. Inputs
are L2-normalized before clustering (tests pin this equivalence to SciPy).

Ward is *reducible*, so the greedy merge order reproduces the NN-chain
dendrogram; cutting at K clusters equals scipy fcluster(criterion="maxclust").

PRODUCTION PATH: on TPU, builds run through ``repro.kernels.ward_pool``
(``ward_assign``), a Pallas kernel that keeps the distance matrix in
VMEM for the whole merge loop and starts from this module's
``ward_distances``; it is bitwise-equal to ``ward_cluster_batch``. Off
the TPU this module IS the path (the kernel's interpreter run is slower
than it). ``PoolingSpec.ward_kernel`` pins either one;
tests/test_kernels_ward.py sweeps the bitwise pin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_INF = jnp.float32(jnp.inf)


def ward_distances(x, mask):
    """Initial squared Ward distances of one document: x [N, d] raw
    token vectors, mask [N] bool -> d2 [N, N] (+inf on masked pairs and
    on the diagonal). Tokens are L2-normalized first (cosine
    clustering). The Pallas kernel starts from these same distances
    (``kernels/ward_pool/ops.py``), so both paths merge identical
    values."""
    N = x.shape[0]
    x = x.astype(jnp.float32)
    nrm = jnp.linalg.norm(x, axis=-1, keepdims=True)
    x = x / jnp.maximum(nrm, 1e-9)
    x = jnp.where(mask[:, None], x, 0.0)
    sq = jnp.sum(x * x, axis=-1)
    # HIGHEST: the TPU's default f32 matmul rounds its inputs to bf16,
    # which would reorder near-tied merges; on the CPU it is the f32 dot
    g = jnp.matmul(x, x.T, precision=jax.lax.Precision.HIGHEST)
    d2 = sq[:, None] + sq[None, :] - 2.0 * g
    d2 = jnp.maximum(d2, 0.0)
    valid_pair = mask[:, None] & mask[None, :]
    eye = jnp.eye(N, dtype=bool)
    return jnp.where(valid_pair & ~eye, d2, _INF)


def _merge_once(d2, sizes, assign, n_active, k_target):
    """One conditional merge step. All shapes static."""
    N = d2.shape[0]
    flat = jnp.argmin(d2.reshape(-1))
    i, j = flat // N, flat % N
    # canonical i < j
    i, j = jnp.minimum(i, j), jnp.maximum(i, j)
    do = (n_active > k_target) & jnp.isfinite(d2[i, j])

    si, sj = sizes[i], sizes[j]
    sc = sizes                                        # [N]
    dij = d2[i, j]
    # Lance-Williams new distances from merged (i) to every k
    denom = si + sj + sc
    new_row = ((si + sc) * d2[i] + (sj + sc) * d2[j] - sc * dij) / \
        jnp.maximum(denom, 1e-9)
    # keep +inf for inactive/self entries
    was_inf = jnp.isinf(d2[i]) | jnp.isinf(d2[j])
    new_row = jnp.where(was_inf, _INF, new_row)
    new_row = new_row.at[i].set(_INF).at[j].set(_INF)

    d2_m = d2.at[i, :].set(new_row).at[:, i].set(new_row)
    d2_m = d2_m.at[j, :].set(_INF).at[:, j].set(_INF)
    sizes_m = sizes.at[i].add(sj).at[j].set(0.0)
    assign_m = jnp.where(assign == j, i, assign)

    d2 = jnp.where(do, d2_m, d2)
    sizes = jnp.where(do, sizes_m, sizes)
    assign = jnp.where(do, assign_m, assign)
    n_active = jnp.where(do, n_active - 1, n_active)
    return d2, sizes, assign, n_active


def ward_cluster(x, mask, k_target):
    """Cluster one document's token vectors.

    Args:
      x: [N, d] float32 token vectors (will be L2-normalized).
      mask: [N] bool validity.
      k_target: scalar int32 — number of clusters to stop at.

    Returns:
      assign: [N] int32 — cluster representative index per token
              (padded tokens keep their own index; mask externally).
    """
    d2 = ward_distances(x, mask)
    sizes = jnp.where(mask, 1, 0).astype(jnp.float32)
    assign = jnp.arange(x.shape[0], dtype=jnp.int32)
    n_active = jnp.sum(mask.astype(jnp.int32))
    k_target = jnp.maximum(jnp.int32(k_target), 1)

    def body(_, state):
        d2, sizes, assign, n_active = state
        return _merge_once(d2, sizes, assign, n_active, k_target)

    N = x.shape[0]
    d2, sizes, assign, n_active = jax.lax.fori_loop(
        0, N - 1, body, (d2, sizes, assign, n_active))
    return assign


@functools.partial(jax.jit, static_argnames=("factor",))
def ward_cluster_batch(x, mask, factor: int):
    """x: [B, N, d]; mask: [B, N]. K per doc = floor(n_valid/factor) + 1.

    Returns assign [B, N] int32.
    """
    n_valid = jnp.sum(mask.astype(jnp.int32), axis=-1)
    k = n_valid // factor + 1
    return jax.vmap(ward_cluster)(x, mask, k)
