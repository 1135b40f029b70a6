"""Activation sharding constraints, applied only when a mesh is ambient.

Models run identically on 1 CPU device (smoke tests) and under the 512-chip
production mesh: `constrain` is a no-op when no mesh is set, and silently
drops axes the ambient mesh doesn't have (e.g. 'pod' on the single-pod mesh)
or that don't divide the dimension.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import PartitionSpec as P

# logical activation axes -> preferred mesh axes, in priority order
_ACT_RULES = {
    "batch": ("pod", "data"),
    "model": ("model",),
    None: (),
}

# Hillclimb override: which mesh axes the activation 'batch' maps to.
# ("pod", "data", "model") turns the model axis into extra data parallelism
# (pure-DP layouts for models that fit a chip).
_BATCH_AXES = contextvars.ContextVar("repro_batch_axes",
                                     default=("pod", "data"))

# Megatron-style sequence parallelism: when set to ("model",), the residual
# stream is sharded along its sequence dim over the model axis at layer
# boundaries — XLA then lowers the TP partial-sums as reduce-scatter (+
# all-gather at next use), halving TP link bytes, and the remat-saved
# boundary activations shrink by the TP degree.
_SEQ_AXES = contextvars.ContextVar("repro_seq_axes", default=())


@contextlib.contextmanager
def act_batch_axes(axes):
    """Temporarily remap the logical 'batch' activation axis (trace-time)."""
    token = _BATCH_AXES.set(tuple(axes))
    try:
        yield
    finally:
        _BATCH_AXES.reset(token)


@contextlib.contextmanager
def act_seq_axes(axes):
    """Enable sequence-parallel boundary sharding (trace-time)."""
    token = _SEQ_AXES.set(tuple(axes))
    try:
        yield
    finally:
        _SEQ_AXES.reset(token)


def use_mesh(mesh):
    """Context manager making ``mesh`` ambient (``jax.set_mesh``).

    Every mesh write routes through here and every read through
    :func:`ambient_mesh`, so :func:`constrain` and jit in_shardings see the
    same mesh.
    """
    return jax.set_mesh(mesh)


def ambient_mesh():
    """The ambient mesh, or None when unset."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def mesh_axis_size(name: str) -> int:
    """Size of a mesh axis in the ambient mesh (1 if absent / no mesh)."""
    mesh = ambient_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get(name, 1)


def constrain(x, *axes):
    """constrain(x, 'batch', None, 'model') — logical per-dim annotation."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    entries = []
    used: set = set()
    for dim, name in zip(x.shape, axes):
        chosen = None
        if name == "batch":
            want = _BATCH_AXES.get()
        elif name == "seq":
            want = _SEQ_AXES.get()
        else:
            want = _ACT_RULES.get(name, (name,) if name else ())
        present = tuple(a for a in want
                        if a in mesh.axis_names and a not in used)
        if present:
            total = 1
            for a in present:
                total *= mesh.shape[a]
            if dim % total == 0:
                chosen = present if len(present) > 1 else present[0]
                used.update(present)
        entries.append(chosen)
    return jax.lax.with_sharding_constraint(x, P(*entries))
