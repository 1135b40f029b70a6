"""Pallas TPU kernel: RG-LRU diagonal gated linear recurrence.

Grid: (D-tiles, L-chunks); D_TILE=128 lanes in parallel, sequence chunks
sequential with the (1, D_TILE) state in VMEM scratch.  Within a chunk the
recurrence is evaluated as a **blocked associative scan**: for a sub-block of
S steps, h_{t+S} = (∏ a) h_t + Σ (suffix-prod a) b — computed with a log₂(S)
Hillis-Steele scan over VMEM tiles instead of S dependent scalar steps, which
is the TPU-native replacement for the GPU's warp-parallel scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

LANE = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, hout_ref, h_ref, *,
                  l_chunk: int):
    li = pl.program_id(1)
    n_l = pl.num_programs(1)

    @pl.when(li == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)   # (l_chunk, D_TILE)
    b = b_ref[...].astype(jnp.float32)

    # Hillis-Steele inclusive scan of the affine maps (a, b) over the chunk:
    # compose (a2, b2) ∘ (a1, b1) = (a2*a1, a2*b1 + b2); log2(l_chunk) rounds.
    steps = max(1, l_chunk.bit_length() - 1)
    if 1 << steps < l_chunk:
        steps += 1

    def compose(off, ab):
        av, bv = ab
        a_shift = jnp.roll(av, off, axis=0)
        b_shift = jnp.roll(bv, off, axis=0)
        row = jax.lax.broadcasted_iota(jnp.int32, av.shape, 0)
        valid = row >= off
        a_new = jnp.where(valid, av * a_shift, av)
        b_new = jnp.where(valid, av * b_shift + bv, bv)
        return a_new, b_new

    av, bv = a, b
    off = 1
    for _ in range(steps):
        av, bv = compose(off, (av, bv))
        off <<= 1

    # y_t = (∏_{s<=t} a_s) h_in + (inclusive-scan b)_t
    h_in = h_ref[0, :]
    y = av * h_in[None, :] + bv
    y_ref[...] = y.astype(y_ref.dtype)
    h_ref[...] = y[-1:, :]

    @pl.when(li == n_l - 1)
    def _finish():
        hout_ref[...] = y[-1:, :].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("d_tile", "l_chunk", "interpret"))
def rglru_scan_pallas(a, b, h0=None, *, d_tile: int = LANE,
                      l_chunk: int = 256, interpret: bool | None = None):
    """Pallas RG-LRU scan; same contract as ref.rglru_scan_ref.

    ``interpret=None`` autodetects: native on a TPU, interpret elsewhere.
    """
    interpret = resolve_interpret(interpret)
    L, D = a.shape
    if h0 is None:
        h0 = jnp.zeros((D,), a.dtype)

    d_pad = _round_up(D, d_tile)
    l_pad = _round_up(L, l_chunk)
    # Padding with a=1, b=0 is the identity affine map.
    a_p = jnp.pad(a, ((0, l_pad - L), (0, d_pad - D)), constant_values=1.0)
    b_p = jnp.pad(b, ((0, l_pad - L), (0, d_pad - D)))
    h0_p = jnp.pad(h0, (0, d_pad - D))[None, :]

    grid = (d_pad // d_tile, l_pad // l_chunk)
    y, h_final = pl.pallas_call(
        functools.partial(_rglru_kernel, l_chunk=l_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((l_chunk, d_tile), lambda d, l: (l, d)),
            pl.BlockSpec((l_chunk, d_tile), lambda d, l: (l, d)),
            pl.BlockSpec((1, d_tile), lambda d, l: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((l_chunk, d_tile), lambda d, l: (l, d)),
            pl.BlockSpec((1, d_tile), lambda d, l: (0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((l_pad, d_pad), a.dtype),
            jax.ShapeDtypeStruct((1, d_pad), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, d_tile), jnp.float32)],
        interpret=interpret,
    )(a_p, b_p, h0_p)
    return y[:L, :D], h_final[0, :D]
