"""Sharding layer: logical-axis constraints are no-ops without a mesh
context and apply inside one."""
import jax
import jax.numpy as jnp

from repro.launch.mesh import make_host_mesh
from repro.sharding.api import constrain, mesh_context


def test_constrain_noop_without_context():
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    assert y is x


def test_constrain_applies_in_context():
    mesh = make_host_mesh()
    with mesh_context(mesh, {"batch": "data"}):
        y = jax.jit(lambda x: constrain(x, "batch", None))(jnp.ones((4, 4)))
    assert y.shape == (4, 4)
