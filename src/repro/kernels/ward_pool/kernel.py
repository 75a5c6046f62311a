"""Batched agglomerative-Ward Pallas kernel: the indexing fast path.

One program clusters a block of ``block_b`` documents with the whole
merge loop fused: the per-doc ``[N, N]`` squared-distance matrix lives
in VMEM for the lifetime of the program (N = doc_maxlen, so
``block_b * N^2 * 4`` bytes — 8 x 256^2 x 4 = 2 MiB at the production
shape), and every merge step is a handful of masked elementwise passes
over it — no HBM round-trip per merge.

Each step is the reference's step (``core/ward.py``), expressed in the
shapes Mosaic lowers: per-doc vectors are rows ``[bb, 1, N]`` (N on
lanes), per-doc scalars are ``[bb, 1, 1]``, and every gather/scatter
of ``_merge_once`` becomes a one-hot select over the resident matrix:

  * select: row minima -> global minimum -> first row holding it ->
    first column in that row. That is ``argmin(d2.reshape(-1))``, the
    reference's row-major tie-break, with no arithmetic, so the merge
    order is bitwise the reference's;
  * Lance-Williams: the same expression, op for op, on the extracted
    rows ``d2[i]`` / ``d2[j]``;
  * update: rows and columns i, j are rewritten in one select. A row
    turns into a column through the diagonal (``where(eye, row, inf)``
    reduced over lanes — an exact copy). Merges the reference skips (k
    reached, or only +inf distances left) write the original rows back
    (``do``-folding), so no-op steps are bitwise no-ops.

The loop runs the block's largest merge budget (scalar-prefetched per
block); steps past a doc's own budget are ``do``-folded no-ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Python float, NOT jnp.float32(inf): a module-level device array would
# be captured as a kernel constant, which pallas_call rejects.
_INF = float("inf")


def _first(hit, iota, n: int, axis: int):
    """Index of the first True along ``axis`` (``n`` if none), keepdims."""
    return jnp.min(jnp.where(hit, iota, n), axis=axis, keepdims=True)


def ward_merge_block(d2, mask, k_target, n_steps):
    """Cluster a block from its initial distances: d2 [bb, N, N] (+inf on
    masked pairs and the diagonal), mask [bb, 1, N] emit mask, k_target
    [bb, 1, 1] cluster target, ``n_steps`` a scalar trip count ->
    assign [bb, 1, N] int32 (representative token index per cluster),
    bitwise == ``ward_cluster_batch``.
    """
    bb, N, _ = d2.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (bb, N, 1), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, 1, N), 2)
    eye = sub == lane                                        # [bb, N, N]

    def to_col(row):
        """[bb, 1, N] row -> [bb, N, 1] column, an exact copy."""
        return jnp.min(jnp.where(eye, row, _INF), axis=2, keepdims=True)

    def row_at(d2, i):
        """Row i of every doc's matrix: [bb, 1, N]."""
        return jnp.min(jnp.where(sub == i, d2, _INF), axis=1, keepdims=True)

    def at(row, i):
        """row[i] per doc: [bb, 1, 1] (exact: one value plus zeros)."""
        return jnp.sum(jnp.where(lane == i, row, 0.0), axis=2, keepdims=True)

    sizes = jnp.where(mask != 0, 1.0, 0.0)                   # [bb, 1, N]
    assign = lane
    n_active = jnp.sum(mask, axis=2, keepdims=True)          # [bb, 1, 1]

    def step(_, state):
        d2, sizes, assign, n_active = state
        # flat row-major argmin: first row holding the global minimum,
        # then the first column in that row
        row_min = jnp.min(d2, axis=2, keepdims=True)         # [bb, N, 1]
        dij = jnp.min(row_min, axis=1, keepdims=True)        # [bb, 1, 1]
        i = _first(row_min == dij, sub, N, axis=1)
        j = _first(row_at(d2, i) == dij, lane, N, axis=2)
        i, j = jnp.minimum(i, j), jnp.maximum(i, j)
        do = (n_active > k_target) & jnp.isfinite(dij)
        d2i, d2j = row_at(d2, i), row_at(d2, j)
        si, sj = at(sizes, i), at(sizes, j)
        sc = sizes
        denom = si + sj + sc
        # Lance-Williams (squared Ward form), same guard as the ref
        new_row = ((si + sc) * d2i + (sj + sc) * d2j
                   - sc * dij) / jnp.maximum(denom, 1e-9)
        oh_i, oh_j = lane == i, lane == j
        was_inf = jnp.isinf(d2i) | jnp.isinf(d2j)
        new_row = jnp.where(was_inf | oh_i | oh_j, _INF, new_row)
        # do-folding: a skipped merge writes the original rows back
        row_i = jnp.where(do, new_row, d2i)
        row_j = jnp.where(do, _INF, d2j)
        col_i, col_j = to_col(row_i), to_col(row_j)
        r_i, r_j = sub == i, sub == j
        d2 = jnp.where(r_j | (lane == j), jnp.where(r_j, row_j, col_j),
                       jnp.where(r_i, row_i, jnp.where(oh_i, col_i, d2)))
        sizes = jnp.where(do, jnp.where(oh_i, si + sj,
                                        jnp.where(oh_j, 0.0, sizes)), sizes)
        assign = jnp.where(do & (assign == j), i, assign)
        n_active = jnp.where(do, n_active - 1, n_active)
        return d2, sizes, assign, n_active

    state = jax.lax.fori_loop(0, n_steps, step,
                              (d2, sizes, assign, n_active))
    return state[2]


def _ward_pool_kernel(steps_ref, d2_ref, mask_ref, k_ref, o_ref):
    """One program = one block of docs; the whole merge loop runs on
    VMEM-resident state."""
    n_steps = steps_ref[pl.program_id(0)]
    o_ref[...] = ward_merge_block(d2_ref[...], mask_ref[...], k_ref[...],
                                  n_steps)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def ward_pool_pallas(d2, mask, k, steps, *, block_b: int = 8,
                     interpret: bool = False):
    """Pallas dispatch: grid over doc blocks (B must be a multiple of
    ``block_b`` — ``ops.ward_assign`` pads with masked docs).

    Args:
      d2: [B, N, N] f32 initial distances (``core.ward.ward_distances``).
      mask: [B, 1, N] int32 emit mask (1 = valid).
      k: [B, 1, 1] int32 per-doc cluster target (``n_valid // factor + 1``).
      steps: [B // block_b] int32 merge budget per block (the max of
        ``n_valid - k`` over its docs).
    Returns assign [B, 1, N] int32.
    """
    B, N, _ = d2.shape
    assert B % block_b == 0, (B, block_b)
    return pl.pallas_call(
        _ward_pool_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // block_b,),
            in_specs=[
                pl.BlockSpec((block_b, N, N), lambda i, s: (i, 0, 0)),
                pl.BlockSpec((block_b, 1, N), lambda i, s: (i, 0, 0)),
                pl.BlockSpec((block_b, 1, 1), lambda i, s: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_b, 1, N), lambda i, s: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), jnp.int32),
        interpret=interpret,
    )(steps, d2, mask, k)


# ---------------------------------------------------------------------------
# Long docs: one doc per program, its matrix walked in row tiles
# ---------------------------------------------------------------------------
def _ward_rows_kernel(steps_ref, d2_hbm, mask_ref, k_ref, o_ref, d2, rmin,
                      rarg, rij, *, rows: int):
    """One doc. Its [N, N] matrix is copied once into VMEM (``d2``) and
    stays there; each merge walks it in tiles of ``rows`` rows, so no
    temporary larger than a tile exists. Row minima and their first
    columns (``rmin``, ``rarg``, [N, 1]) are kept up to date by the
    update pass, so the selection reads [N] values, not [N, N]:
    the first row holding the global minimum, then (its ``rarg``) the
    first column in that row — the flat row-major argmin of the
    reference. Lance-Williams and the row/column rewrite are the
    resident kernel's expressions, tile by tile, so the merges are
    bitwise ``ward_cluster_batch``'s; a tile's piece of columns i and j
    is its diagonal block's slice of rows i and j (``rij``), turned
    into a column through that [rows, rows] block's diagonal."""
    N = d2.shape[0]
    n_tiles = N // rows
    pltpu.sync_copy(d2_hbm.at[pl.program_id(0)], d2)
    mask, k_target = mask_ref[0], k_ref[0]                  # [1,N], [1,1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    diag = tile_row == jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    col_row = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)

    def tile_at(t):
        r0 = pl.multiple_of(t * rows, rows)
        return r0, d2[pl.ds(r0, rows), :]

    def set_minima(r0, tile):
        m = jnp.min(tile, axis=1, keepdims=True)              # [rows, 1]
        rmin[pl.ds(r0, rows), :] = m
        rarg[pl.ds(r0, rows), :] = _first(tile == m, lane, N, axis=1)

    def init_tile(t, _):
        set_minima(*tile_at(t))
        return 0

    jax.lax.fori_loop(0, n_tiles, init_tile, 0)

    def at(row, i):
        return jnp.sum(jnp.where(lane == i, row, 0.0), axis=1, keepdims=True)

    def step(_, state):
        sizes, assign, n_active = state
        rm = rmin[...]                                        # [N, 1]
        dij = jnp.min(rm, axis=0, keepdims=True)              # [1, 1]
        i0 = _first(rm == dij, col_row, N, axis=0)
        j0 = jnp.sum(jnp.where(col_row == i0, rarg[...], 0), axis=0,
                     keepdims=True)
        i, j = jnp.minimum(i0, j0), jnp.maximum(i0, j0)

        def rows_ij(t, carry):
            d2i, d2j = carry
            r0, tile = tile_at(t)
            r = r0 + tile_row
            return (jnp.minimum(d2i, jnp.min(jnp.where(r == i, tile, _INF),
                                             axis=0, keepdims=True)),
                    jnp.minimum(d2j, jnp.min(jnp.where(r == j, tile, _INF),
                                             axis=0, keepdims=True)))

        inf_row = jnp.full((1, N), _INF, jnp.float32)
        d2i, d2j = jax.lax.fori_loop(0, n_tiles, rows_ij, (inf_row, inf_row))
        do = (n_active > k_target) & jnp.isfinite(dij)
        si, sj = at(sizes, i), at(sizes, j)
        sc = sizes
        denom = si + sj + sc
        new_row = ((si + sc) * d2i + (sj + sc) * d2j
                   - sc * dij) / jnp.maximum(denom, 1e-9)
        oh_i, oh_j = lane == i, lane == j
        was_inf = jnp.isinf(d2i) | jnp.isinf(d2j)
        new_row = jnp.where(was_inf | oh_i | oh_j, _INF, new_row)
        row_i = jnp.where(do, new_row, d2i)
        row_j = jnp.where(do, _INF, d2j)
        rij[0:1, :] = row_i
        rij[1:2, :] = row_j

        def update(t, _):
            r0, tile = tile_at(t)
            r = r0 + tile_row
            seg = rij[:, pl.ds(r0, rows)]                     # [8, rows]
            col_i = jnp.min(jnp.where(diag, seg[0:1], _INF), axis=1,
                            keepdims=True)
            col_j = jnp.min(jnp.where(diag, seg[1:2], _INF), axis=1,
                            keepdims=True)
            r_i, r_j = r == i, r == j
            tile = jnp.where(r_j | oh_j, jnp.where(r_j, row_j, col_j),
                             jnp.where(r_i, row_i,
                                       jnp.where(oh_i, col_i, tile)))
            d2[pl.ds(r0, rows), :] = tile
            set_minima(r0, tile)
            return 0

        jax.lax.fori_loop(0, n_tiles, update, 0)
        sizes = jnp.where(do, jnp.where(oh_i, si + sj,
                                        jnp.where(oh_j, 0.0, sizes)), sizes)
        assign = jnp.where(do & (assign == j), i, assign)
        n_active = jnp.where(do, n_active - 1, n_active)
        return sizes, assign, n_active

    state = (jnp.where(mask != 0, 1.0, 0.0), lane,
             jnp.sum(mask, axis=1, keepdims=True))
    o_ref[0] = jax.lax.fori_loop(0, steps_ref[pl.program_id(0)], step,
                                 state)[1]


def rows_vmem_bytes(N: int, rows: int) -> int:
    """VMEM the long-doc kernel asks for: the resident [N, N] f32 matrix,
    its two [N, 1] minima caches (lane-padded to 128), rows i and j, and
    about a dozen live [rows, N] f32 tile temporaries, with 4 MiB to
    spare."""
    return (4 * N * N + 2 * 4 * 128 * N + 4 * 8 * N + 12 * 4 * rows * N
            + (4 << 20))


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def ward_pool_rows_pallas(d2, mask, k, steps, *, rows: int = 128,
                          interpret: bool = False):
    """The long-doc kernel: same arguments and result as
    ``ward_pool_pallas`` with one doc per program (``steps`` [B], each
    doc's own merge budget); N must be a multiple of ``rows``."""
    B, N, _ = d2.shape
    assert N % rows == 0, (N, rows)
    one = lambda b, s: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_ward_rows_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, 1, N), one),
                      pl.BlockSpec((1, 1, 1), one)],
            out_specs=pl.BlockSpec((1, 1, N), one),
            scratch_shapes=[pltpu.VMEM((N, N), jnp.float32),
                            pltpu.VMEM((N, 1), jnp.float32),
                            pltpu.VMEM((N, 1), jnp.int32),
                            pltpu.VMEM((8, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=rows_vmem_bytes(N, rows)),
        interpret=interpret,
    )(steps, d2, mask, k)
