"""Mesh builders: the local host mesh, and the serving mesh and device
table that ``core/replicated.py`` places index shards on.

Functions, not module constants: importing this module must never touch
jax device state (a caller may set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import List

import jax


def make_host_mesh():
    """Whatever devices exist locally, as a 1D 'data' mesh (tests/smoke)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=_auto(1))


def _auto(n: int):
    """Auto axis types: shardings are propagated and constrained with
    ``with_sharding_constraint`` (``jax.make_mesh`` defaults to Explicit)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_serve_mesh(n_replicas: int, n_shards: int):
    """The scale-out serving mesh: axes ("replica", "shard").

    Replica groups are pure throughput parallelism (each group serves
    whole microbatches); the shard axis partitions the corpus inside a
    group (core/replicated.py places index shards along it and merges
    top-k with a collective). Requires ``n_replicas * n_shards``
    devices; use :func:`serve_device_table` when the host has fewer —
    placement degrades to round-robin reuse, losing parallelism but
    never parity.
    """
    assert n_replicas >= 1 and n_shards >= 1, (n_replicas, n_shards)
    need = n_replicas * n_shards
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(f"serve mesh ({n_replicas} replicas x "
                         f"{n_shards} shards) needs {need} devices, "
                         f"host has {len(devs)}")
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:need]).reshape(n_replicas, n_shards),
                ("replica", "shard"))


def make_shard_mesh(devices):
    """A 1-D ("shard",) mesh over one replica group's device row — the
    mesh ``core/replicated.py`` shard_maps a group's dense scan over."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(list(devices)), ("shard",))


def serve_device_table(n_replicas: int, n_shards: int
                       ) -> List[List[object]]:
    """Device placement for (replica, shard) cells, tiling the local
    devices round-robin when there are fewer than ``n_replicas *
    n_shards`` — single-device hosts get the whole table on device 0
    (bitwise-identical serving, no parallelism), an 8-device host gives
    4x2 its own device per cell. ``table[r][s]`` is shard ``s`` of
    replica group ``r``."""
    assert n_replicas >= 1 and n_shards >= 1, (n_replicas, n_shards)
    devs = jax.devices()
    return [[devs[(r * n_shards + s) % len(devs)]
             for s in range(n_shards)] for r in range(n_replicas)]


def distinct_row(row) -> bool:
    """True when a replica group's device row has no reuse — the
    precondition for building a real shard mesh over it."""
    return len({d.id for d in row}) == len(row)
