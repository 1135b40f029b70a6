"""Pallas TPU kernels (validated in interpret mode on CPU; ops.py wrappers
select kernel vs pure-jnp oracle via use_pallas).

* ticket_dispatch — prefix-sum ticketing for MoE slot assignment (the
  paper's fetch-and-add doorway, TPU-native).
* mamba_scan     — Mamba-1 selective scan (falcon-mamba hot spot).
* rglru          — RG-LRU gated linear recurrence (recurrentgemma hot spot).

All kernels (and the lockVM's ``mode="pallas"`` sweep driver in
``repro.sim.engine_pallas``) share :func:`default_interpret` to decide
whether ``pallas_call`` should compile natively or run the interpreter:
natively exactly on a TPU, the one accelerator this repo targets.  Every
entry point keeps ``interpret`` overridable (and jit-static), so tests can
force the interpreter on a device and device runs can be forced from
CPU-hosted tracing.
"""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """True when ``pallas_call`` must interpret (the backend is no TPU).

    Resolved at trace time: callers take ``interpret: bool | None = None``
    as a jit-static argument and substitute this when it is None, so the
    chosen value is baked into the compiled executable per backend.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` if explicitly given, else the backend default."""
    return default_interpret() if interpret is None else bool(interpret)
