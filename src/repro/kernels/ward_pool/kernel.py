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

Past N = 724 one doc's matrix fills the budget alone, and walking it
every merge costs O(N^2) a merge. The long-doc kernel
(``ward_pool_rows_pallas``) instead keeps each row's minimum exact as
the matrix changes, so a merge reads and writes O(N) values: rows i and
j, two column stripes, and the rows whose minimum moved elsewhere.
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
# Long docs: one doc per program, O(N) work a merge
# ---------------------------------------------------------------------------
def _first2(hit, col, n: int):
    """First True flat index of a [S, W] mask (``n`` if none): [1, 1]."""
    return jnp.min(_first(hit, col, n, axis=1), axis=0, keepdims=True)


def _min2(x):
    """Minimum of a [S, W] array: [1, 1]."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _sum2(x):
    """Sum of a [S, W] array: [1, 1]."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _at(x, hit):
    """The one entry of a [S, W] array where ``hit``: [1, 1] (exact:
    one value plus zeros)."""
    return _sum2(jnp.where(hit, x, jnp.zeros_like(x)))


def _transpose_broadcast(v):
    """A [1, W] row -> [W, W] with ``out[t, :] = v[t]``, or a [W, 1]
    column -> [W, W] with ``out[:, t] = v[t]``: exact copies through a
    transpose."""
    W = max(v.shape)
    return jnp.transpose(jnp.broadcast_to(v, (W, W)))


def _ward_stripes_kernel(steps_ref, d2_hbm, mask_ref, k_ref, o_ref, n_ref,
                         d2, rmin, rarg, sizes, *, lanes: int):
    """One doc. Its [N, N] matrix is copied once into VMEM as S = N / W
    column stripes of W lanes, stacked: ``d2[c * N + r, l]`` is entry
    (r, c * W + l). Column c * W + l is lane l of stripe c; row r is
    the strided sublanes ``r, N + r, ...``, which read as [S, W] — the
    layout of every per-column vector here (flat index s * W + l).

    The matrix is kept whole and symmetric, and ``rmin``/``rarg`` hold
    every row's minimum and the first column holding it, exactly. A
    merge then touches O(N) values:

      * select: the first row holding the global minimum, its ``rarg``
        — ``argmin(d2.reshape(-1))``, the reference's flat row-major
        tie-break;
      * Lance-Williams on rows i and j, the reference's expression op
        for op; row and column i take the new distances, row and
        column j +inf (a column through its stripe, with the row
        turned into sublanes by a transpose — an exact copy);
      * minima: only entries (k, i) and (k, j) of another row k
        changed, so with v = new[k] its minimum becomes (v, i) if v is
        lower, its first column i if v ties and i comes first, or i if
        its old first column was i or j and v ties (no column before j
        held the minimum). If that column was i or j and v is higher,
        row k is read again (a rescan, counted in ``n_ref``).

    Merges the reference skips (k reached, or only +inf left) write
    nothing."""
    N, W = d2_hbm.shape[1], lanes
    S = N // W
    b = pl.program_id(0)
    for c in range(S):
        pltpu.sync_copy(d2_hbm.at[b, :, pl.ds(c * W, W)],
                        d2.at[pl.ds(c * N, N), :])
    col = (jax.lax.broadcasted_iota(jnp.int32, (S, W), 0) * W
           + jax.lax.broadcasted_iota(jnp.int32, (S, W), 1))
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    inf_row = jnp.full((S, W), _INF, jnp.float32)

    def read_row(r):
        return d2[pl.ds(r, S, stride=N), :]

    def write_row(r, row):
        d2[pl.ds(r, S, stride=N), :] = row

    # initial minima, one walk: per block of W rows, stripe by stripe
    # (a strictly lower minimum wins, so the first column is kept)
    def init_block(t, _):
        r0 = pl.multiple_of(t * W, W)

        def scan(c, best):
            tile = d2[pl.ds(c * N + r0, W), :]                # [W, W]
            m = jnp.min(tile, axis=1, keepdims=True)
            a = _first(tile == m, lane_w, W, axis=1) + c * W
            lt = m < best[0]
            return jnp.where(lt, m, best[0]), jnp.where(lt, a, best[1])

        m, a = jax.lax.fori_loop(
            0, S, scan, (jnp.full((W, 1), _INF, jnp.float32),
                         jnp.zeros((W, 1), jnp.int32)))
        rmin[pl.ds(t, 1), :] = _transpose_broadcast(m)[0:1]
        rarg[pl.ds(t, 1), :] = _transpose_broadcast(a)[0:1]
        return 0

    jax.lax.fori_loop(0, S, init_block, 0)
    mask = mask_ref[0]                                        # [S, W]
    sizes[...] = jnp.where(mask != 0, 1.0, 0.0)
    o_ref[0] = col
    k_target = k_ref[0]                                       # [1, 1]

    def merge(i, j, iv, jv, dij):
        d2i, d2j = read_row(i), read_row(j)
        sz = sizes[...]
        si, sj = _at(sz, col == iv), _at(sz, col == jv)
        sc = sz
        denom = si + sj + sc
        # Lance-Williams (squared Ward form), same guard as the ref
        new = ((si + sc) * d2i + (sj + sc) * d2j
               - sc * dij) / jnp.maximum(denom, 1e-9)
        oh_i, oh_j = col == iv, col == jv
        was_inf = jnp.isinf(d2i) | jnp.isinf(d2j)
        new = jnp.where(was_inf | oh_i | oh_j, _INF, new)
        write_row(i, new)
        write_row(j, inf_row)
        ci, li = i // W, i % W
        for s in range(S):
            rows = pl.ds(pl.multiple_of(ci * N + s * W, W), W)
            d2[rows, :] = jnp.where(lane_w == li,
                                    _transpose_broadcast(new[s:s + 1]),
                                    d2[rows, :])
        cj = pl.ds(pl.multiple_of(j // W * N, N), N)
        d2[cj, :] = jnp.where(lane_w[0:1] == j % W, _INF, d2[cj, :])
        sizes[...] = jnp.where(oh_i, si + sj, jnp.where(oh_j, 0.0, sz))
        o_ref[0] = jnp.where(o_ref[0] == jv, iv, o_ref[0])

        rm, ra = rmin[...], rarg[...]
        lt, eq = new < rm, new == rm
        hit = (ra == iv) | (ra == jv)
        mi = _min2(new)
        rmin[...] = jnp.where(oh_i, mi, jnp.where(oh_j, _INF,
                                                 jnp.where(lt, new, rm)))
        rarg[...] = jnp.where(
            oh_i, _first2(new == mi, col, N),
            jnp.where(oh_j, 0, jnp.where(lt | (eq & (hit | (iv < ra))),
                                         iv, ra)))
        marked = jnp.where(hit & (new > rm) & ~oh_i & ~oh_j, 1, 0)

        def rescan(c):
            k, marked, n = c                                  # k: scalar
            row = read_row(k)
            m = _min2(row)
            at_k = col == k
            rmin[...] = jnp.where(at_k, m, rmin[...])
            rarg[...] = jnp.where(at_k, _first2(row == m, col, N), rarg[...])
            marked = jnp.where(at_k, 0, marked)
            return _first2(marked > 0, col, N)[0, 0], marked, n + 1

        return jax.lax.while_loop(
            lambda c: c[0] < N, rescan,
            (_first2(marked > 0, col, N)[0, 0], marked, jnp.int32(0)))[2]

    def step(_, carry):
        n_active, n_rescans = carry                   # [1, 1], scalar
        rm = rmin[...]
        dij = _min2(rm)
        i0 = _first2(rm == dij, col, N)
        j0 = _at(rarg[...], col == i0)
        do = (n_active > k_target) & jnp.isfinite(dij)
        # a skipped merge reads i = N
        iv = jnp.where(do, jnp.minimum(i0, j0), N)
        jv = jnp.maximum(i0, j0)
        i = iv[0, 0]
        n = jax.lax.cond(i < N, lambda: merge(i, jv[0, 0], iv, jv, dij),
                         lambda: jnp.int32(0))
        return (jnp.where(do, n_active - 1, n_active), n_rescans + n)

    _, n = jax.lax.fori_loop(
        0, steps_ref[b], step,
        (_sum2(mask), jnp.int32(0)))
    n_ref[0] = jnp.full((1, 1), n, jnp.int32)


def stripes_vmem_bytes(N: int, lanes: int) -> int:
    """VMEM the long-doc kernel asks for: the resident [N, N] f32
    matrix, a few live [N, lanes] stripe temporaries, with 4 MiB to
    spare (the per-column vectors are [N / lanes, lanes], a few KiB)."""
    return 4 * N * N + 4 * 4 * N * lanes + (4 << 20)


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def ward_pool_rows_pallas(d2, mask, k, steps, *, lanes: int = 128,
                          interpret: bool = False):
    """The long-doc kernel, one doc per program.

    Args:
      d2: [B, N, N] f32 initial distances (``core.ward.ward_distances``);
        N a multiple of ``lanes`` and of 8.
      mask: [B, N // lanes, lanes] int32 emit mask (1 = valid).
      k: [B, 1, 1] int32 per-doc cluster target.
      steps: [B] int32 each doc's merge budget (``n_valid - k``).
    Returns (assign [B, N // lanes, lanes] int32, bitwise
    ``ward_cluster_batch``'s, flattened row-major; rescans [B, 1, 1]
    int32, the rows each doc read again to keep its row minima).
    """
    B, N, _ = d2.shape
    S = N // lanes
    assert N == S * lanes and N % 8 == 0, (N, lanes)
    one = lambda b, s: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_ward_stripes_kernel, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, S, lanes), one),
                      pl.BlockSpec((1, 1, 1), one)],
            out_specs=[pl.BlockSpec((1, S, lanes), one),
                       pl.BlockSpec((1, 1, 1), one)],
            scratch_shapes=[pltpu.VMEM((S * N, lanes), jnp.float32),
                            pltpu.VMEM((S, lanes), jnp.float32),
                            pltpu.VMEM((S, lanes), jnp.int32),
                            pltpu.VMEM((S, lanes), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, S, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=stripes_vmem_bytes(N, lanes)),
        interpret=interpret,
    )(steps, d2, mask, k)
