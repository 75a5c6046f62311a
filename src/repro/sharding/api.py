"""Logical-axis sharding constraints.

Code annotates arrays with *logical* axis names
(``constrain(x, "docs", None, None)``, ``logical_spec("docs")``); a
``MeshContext`` maps logical names to physical mesh axes. With no active
context every annotation is a no-op, so the same code runs on one device
and on a serving mesh unchanged. ``serve_rules`` is the rule set of the
scale-out serving mesh (``core/replicated.py``).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

_STATE = threading.local()


@dataclass
class MeshContext:
    mesh: Mesh
    rules: Dict[str, Axis] = field(default_factory=dict)

    def resolve(self, name: Optional[str]) -> Axis:
        if name is None:
            return None
        return self.rules.get(name, None)


def current_ctx() -> Optional[MeshContext]:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: Dict[str, Axis]):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = MeshContext(mesh, dict(rules))
    try:
        yield _STATE.ctx
    finally:
        _STATE.ctx = prev


def logical_spec(*names: Optional[str]) -> P:
    ctx = current_ctx()
    if ctx is None:
        return P()
    return P(*[ctx.resolve(n) for n in names])


def constrain(x, *names: Optional[str]):
    """Apply with_sharding_constraint if a mesh context is active."""
    ctx = current_ctx()
    if ctx is None:
        return x
    assert len(names) == x.ndim, (names, x.shape)
    spec = P(*[ctx.resolve(n) for n in names])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Standard rule sets
# ---------------------------------------------------------------------------
def serve_rules(shard_axis: str = "shard",
                replica_axis: str = "replica") -> Dict[str, Axis]:
    """Scale-out serving (launch/mesh.make_serve_mesh): the doc axis
    partitions over the shard axis inside a replica group; queries are
    replicated (every shard scores the whole microbatch, the top-k
    merge is the only collective). The batch axis maps to the replica
    axis only for router-level accounting — the engine routes whole
    microbatches to replica groups rather than splitting rows."""
    return {
        "docs": shard_axis,
        "queries": None,
        "tokens": None,
        "dim": None,
        "centroids": None,
        "batch": replica_axis,
    }
