"""Smoke coverage for the mesh helpers and the dry-run launcher's use of them.

Every mesh the repo makes ambient goes through `shard_utils`
(`ambient_mesh()` for reads, `use_mesh()` for writes), so `constrain` and
jit in_shardings see the same mesh.  These tests pin the helpers'
behaviour on the installed jax and dryrun's use of them.
"""

import inspect
import os

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.shard_utils import ambient_mesh, constrain, use_mesh


def _one_device_mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


def test_use_mesh_makes_mesh_ambient():
    """use_mesh must make the mesh ambient and leave no ambient mesh
    behind on exit."""
    mesh = _one_device_mesh()
    assert ambient_mesh() is None
    with use_mesh(mesh):
        ambient = ambient_mesh()
        assert ambient is not None
        assert dict(ambient.shape) == {"data": 1}
        # constrain() must be usable under the ambient mesh
        x = constrain(jnp.ones((4, 2)), "batch", None)
        assert x.shape == (4, 2)
    assert ambient_mesh() is None


def test_use_mesh_composes_with_jit():
    mesh = _one_device_mesh()
    with use_mesh(mesh):
        y = jax.jit(lambda v: constrain(v * 2, "batch"))(jnp.arange(4.0))
    assert np.array_equal(np.asarray(y), [0.0, 2.0, 4.0, 6.0])


def test_dryrun_imports_and_routes_mesh_through_shim():
    """Importing dryrun must succeed, and its mesh entry must be the
    shard_utils helper, not jax.set_mesh."""
    saved = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch import dryrun
    finally:  # dryrun pins XLA_FLAGS for its own 512-device use; undo
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved
    src = inspect.getsource(dryrun.run_cell)
    assert "use_mesh(mesh)" in src
    assert "jax.set_mesh" not in src
