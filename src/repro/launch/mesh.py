"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data parallelism across pods (gradients all-reduce over
("pod", "data") — DCN-friendly: only one collective crosses pods).

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import List

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


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


def batch_axes(mesh) -> object:
    """The data-parallel axis spec for this mesh ('data' or (pod, data))."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def fsdp_axes(mesh) -> object:
    """Weight-sharding (ZeRO) axes: same as the DP axes."""
    return batch_axes(mesh)
