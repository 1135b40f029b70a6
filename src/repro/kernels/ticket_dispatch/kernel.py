"""Pallas TPU kernel: prefix-sum ticketing for MoE dispatch.

Grid: 1-D over token blocks, executed **sequentially** (TPU grid dims are
sequential by default) so a VMEM scratch accumulator carries the per-expert
ticket counters across blocks — the kernel-resident analogue of the ticket
lock's central ``ticket`` field, advanced once per block instead of once per
arrival (one MXU-friendly reduction replaces N serialized fetch-and-adds).

Tiling: arrivals are flattened to (BLOCK_N,) per grid step and one-hot
expanded to (BLOCK_N, E_pad) in VMEM with E_pad a multiple of 128 (lane
dimension); BLOCK_N is a multiple of 8 (sublanes).  The one-hot matrix never
touches HBM — only ids in, tickets out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

LANE = 128
SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _ticket_kernel(ids_ref, tickets_ref, counters_ref, *, n_experts_pad: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counters_ref[...] = jnp.zeros_like(counters_ref)

    ids = ids_ref[...]                                   # (1, BLOCK_N) int32
    iota_e = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[1], n_experts_pad), 1)
    onehot = (ids[0, :, None] == iota_e).astype(jnp.int32)   # (BLOCK_N, E_pad)
    exclusive = jnp.cumsum(onehot, axis=0) - onehot          # in-block prefix
    base = counters_ref[...]                                 # (1, E_pad)
    ticket_mat = exclusive + base                            # broadcast row
    mine = jnp.sum(ticket_mat * onehot, axis=1)              # (BLOCK_N,)
    counters_ref[...] = base + jnp.sum(onehot, axis=0, keepdims=True)
    tickets_ref[...] = mine[None, :]


@functools.partial(jax.jit, static_argnames=("n_experts", "block_n", "interpret"))
def ticket_dispatch_pallas(expert_ids: jnp.ndarray, n_experts: int,
                           block_n: int = 1024,
                           interpret: bool | None = None) -> jnp.ndarray:
    """FIFO tickets for a flat int32 arrival sequence (any shape, flattened).

    ``interpret=None`` autodetects: native on a TPU, interpret elsewhere
    (:func:`repro.kernels.default_interpret`); an explicit bool wins.
    """
    interpret = resolve_interpret(interpret)
    shape = expert_ids.shape
    flat = expert_ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    e_pad = _round_up(max(n_experts, 1), LANE)
    bn = min(_round_up(block_n, SUBLANE), _round_up(n, SUBLANE))
    n_pad = _round_up(n, bn)
    # Padding ids with -1 never matches an expert column -> tickets unaffected.
    flat = jnp.pad(flat, (0, n_pad - n), constant_values=-1)[None, :]  # (1, n_pad)

    grid = (n_pad // bn,)
    out = pl.pallas_call(
        functools.partial(_ticket_kernel, n_experts_pad=e_pad),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bn), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, e_pad), jnp.int32)],
        interpret=interpret,
    )(flat)
    return out[0, :n].reshape(shape)
