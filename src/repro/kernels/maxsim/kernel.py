"""MaxSim Pallas TPU kernels: corpus scan and gathered-candidate rerank.

Both run one program per (query, slab of ``block`` docs). Each doc of
the slab is one MXU matmul ``[Lq, dim] x [dim, Ld]``, then the masked
max over doc tokens and sum over query tokens reduce it to one score.
The only difference is where the slab comes from: the shared corpus
(``maxsim_pallas``) or the query's own candidate gather
(``maxsim_rerank_pallas``).

Layout (Mosaic wants the last two block dims to be multiples of
(8, 128) or the array's own): the query mask rides as a ``[Lq, 1]``
column, and scores leave through an output of shape ``[Nq, 1, S]``
whose lane tile (``out_lanes``) stays resident while the consecutive
programs that own its lanes fill them in (``place``). The wrapper pads
the doc axis with ``pad_slots``.

VMEM per program (f32, block=8, Ld=256, dim=128): the doc slab is
1 MiB (2 MiB double-buffered) and one ``[Lq, Ld]`` similarity tile is
32 KiB — far under the default scoped limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def out_lanes(n: int) -> int:
    """Lane width of one output tile over an axis of ``n`` padded slots:
    the whole axis while it fits one 128-lane tile, else 128."""
    return n if n <= LANES else LANES


def pad_slots(n: int, block: int) -> int:
    """Slot count the kernels need: a multiple of ``block``, and a
    multiple of 128 once it spans more than one lane tile."""
    n = -(-max(n, 1) // block) * block
    return n if n <= LANES else -(-n // LANES) * LANES


def maxsim_from_sim(sim, qm, dm):
    """sim [Lq, Ld] token similarities, qm [Lq, 1] / dm [1, Ld] bool ->
    [1, 1] MaxSim: max over valid doc tokens, summed over valid query
    tokens (a doc with no valid token scores 0)."""
    best = jnp.max(jnp.where(dm, sim, -jnp.inf), axis=1, keepdims=True)
    best = jnp.where(qm & jnp.isfinite(best), best, 0.0)
    return jnp.sum(best, axis=0, keepdims=True)


def place(tile, scores, j, block: int):
    """Write program ``j``'s ``block`` scores ([1, 1] each) into the
    lanes it owns of the resident output tile [1, T]."""
    T = tile.shape[-1]
    base = (j % (T // block)) * block
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    for b, s in enumerate(scores):
        tile = jnp.where(lane == base + b, s, tile)
    return tile


def compiler_params():
    """Queries are independent; the slab axis revisits an output tile."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def slab_out_spec(S: int, block: int):
    """Output spec over [Nq, 1, S]: the lane tile program (i, j) fills."""
    T = out_lanes(S)
    return pl.BlockSpec((None, 1, T), lambda i, j: (i, 0, j // (T // block)))


def _maxsim_kernel(q_ref, qm_ref, d_ref, dm_ref, o_ref):
    """One query x one slab of docs: q [Lq, dim], qm [Lq, 1],
    d [block, Ld, dim], dm [block, Ld] -> lanes of o [1, T]."""
    q = q_ref[...].astype(jnp.float32)
    qm = qm_ref[...] != 0
    dm = dm_ref[...] != 0
    scores = []
    for b in range(d_ref.shape[0]):
        sim = jax.lax.dot_general(q, d_ref[b].astype(jnp.float32),
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        scores.append(maxsim_from_sim(sim, qm, dm[b:b + 1]))
    o_ref[...] = place(o_ref[...], scores, pl.program_id(1),
                       d_ref.shape[0])


def _call(q, q_mask, d, d_mask, d_spec, dm_spec, S, block, interpret):
    Nq, Lq, dim = q.shape
    return pl.pallas_call(
        _maxsim_kernel,
        grid=(Nq, S // block),
        in_specs=[
            pl.BlockSpec((None, Lq, dim), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, Lq, 1), lambda i, j: (i, 0, 0)),
            d_spec, dm_spec,
        ],
        out_specs=slab_out_spec(S, block),
        out_shape=jax.ShapeDtypeStruct((Nq, 1, S), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(q, q_mask, d, d_mask)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def maxsim_pallas(q, q_mask, d, d_mask, *, block_d: int = 8,
                  interpret: bool = False):
    """Corpus scan: q [Nq, Lq, dim], q_mask [Nq, Lq, 1] int32; d
    [Nd, Ld, dim], d_mask [Nd, Ld] int32 -> scores [Nq, 1, Nd] f32.
    Nd == ``pad_slots(Nd, block_d)`` (the wrapper pads)."""
    Nd, Ld, dim = d.shape
    assert Nd == pad_slots(Nd, block_d), (Nd, block_d)
    return _call(q, q_mask, d, d_mask,
                 pl.BlockSpec((block_d, Ld, dim), lambda i, j: (j, 0, 0)),
                 pl.BlockSpec((block_d, Ld), lambda i, j: (j, 0)),
                 Nd, block_d, interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def maxsim_rerank_pallas(q, q_mask, d, d_mask, *, block_s: int = 8,
                         interpret: bool = False):
    """Gathered-candidate rerank: q [Nq, Lq, dim], q_mask [Nq, Lq, 1]
    int32; d [Nq, S, Ld, dim], d_mask [Nq, S, Ld] int32 -> scores
    [Nq, 1, S] f32; query i scores only its own slab d[i].
    S == ``pad_slots(S, block_s)`` (the wrapper pads)."""
    _, S, Ld, dim = d.shape
    assert S == pad_slots(S, block_s), (S, block_s)
    return _call(q, q_mask, d, d_mask,
                 pl.BlockSpec((None, block_s, Ld, dim),
                              lambda i, j: (i, j, 0, 0)),
                 pl.BlockSpec((None, block_s, Ld), lambda i, j: (i, j, 0)),
                 S, block_s, interpret)
