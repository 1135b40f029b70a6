"""Pallas fast path for the lockVM sweep engine (``mode="pallas"``).

The map/vmap/sched drivers in :mod:`repro.sim.engine` all round-trip the
full :class:`~repro.sim.engine.SimState` through a ``lax.while_loop`` carry
once per *event*: every single-event step is a host-visible XLA loop
iteration, so the per-step dispatch and carry traffic dominate wall-clock
on wide devices.  This module instead runs the single-event step — the
fused argmin event selection over ``[pending-commit times | thread
next_time]``, the opcode switch producing a compact ``Effects`` record,
and the packed-bitset sharer update — *inside* one ``pallas_call``: the
grid is one step per sweep cell, each grid step loads that cell's whole
hot state (``SimState`` arrays including the ``(n_lines, ceil(T/32))
uint32`` sharer bitsets) into kernel memory once, executes events in
``chunk``-sized bursts (an in-kernel ``fori_loop`` inside a termination
``while_loop``) and writes only the final stats back out.  State lives in
kernel-resident buffers across the whole burst instead of being carried
through an XLA loop boundary per event.

Bit-identity is by construction, not by parallel reimplementation: the
kernel body calls the very same :func:`repro.sim.engine._step` transition
the other three drivers use, so :data:`repro.sim.engine.
EVENT_ORDER_CONTRACT` — commit-wins tie-break, int32 wrap semantics,
collision counters, everything — holds verbatim.  The self-guarding step
(a cell past its horizon/event budget dispatches the no-event pseudo-op)
makes burst overshoot free: running up to ``chunk - 1`` extra steps after
termination is an exact identity, so per-cell results match ``mode="map"``
bit for bit.  The differential fuzzer (``repro.sim.check``) diffs this
driver against the NumPy oracle alongside the other modes.

Backend story: with ``interpret=True`` (the CPU default via
:func:`repro.kernels.default_interpret`) the kernel is discharged to
ordinary XLA and serves as the correctness reference.  It does not lower
for TPU: :func:`repro.sim.engine.run_sweep` raises :data:`TPU_REFUSAL`
instead of dropping to the interpreter, and ``mode="auto"`` never picks it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import isa
from .engine import INF, N_LAT_BUCKETS, SimConsts, _initial_state, _step

# Events per in-kernel burst between termination checks.  The burst loop
# costs ``ceil(events / chunk) * chunk`` steps per cell (overshoot steps are
# identity no-events), so the waste is bounded by ``chunk - 1`` steps per
# cell while the termination reduction is amortized over ``chunk`` events.
DEFAULT_PALLAS_CHUNK = 128

# Why run_sweep refuses mode="pallas" natively: what Mosaic, the TPU Pallas
# compiler, says of this kernel when compiled for a v5e.
TPU_REFUSAL = (
    "mode='pallas' does not lower for TPU. Mosaic refuses the sweep "
    "kernel's (1, n_threads) and (1,) blocks: the last two block dimensions "
    "must divide by 8 and 128 or span the array. With those worked around, "
    "it refuses the int32 argmin in engine._step: 'Only float32 is "
    "supported'. Use mode='auto', 'map', 'vmap' or 'sched'.")

# Result keys, in kernel-output order (the engine's sweep-output contract).
OUT_KEYS = ("acquisitions", "waited_acquisitions", "handover_sum",
            "handover_count", "events", "sleeping", "grant_value",
            "lat_hist")


def make_run_pallas(n_threads: int, mem_words: int, n_locks: int,
                    prog_len: int, chunk: int, interpret: bool,
                    n_faults: int = 0):
    """Build the ``mode="pallas"`` sweep driver for one shape set.

    Same signature as the other ``_make_run_*`` drivers: the returned
    function takes the batched sweep arrays (leading axis B) and returns
    the stacked per-cell stats dict.  ``chunk`` and ``interpret`` are
    compile-time constants (part of the ``_build_engine`` cache key), as is
    ``n_faults`` — when > 0 the driver takes four trailing ``(B, n_faults)``
    fault-schedule arrays and the kernel's step gains the fault phase (the
    no-event identity still holds for overshoot steps: faults only apply
    while the cell is live, so burst overshoot remains free).
    """
    assert chunk >= 1, chunk
    n_lines = mem_words // isa.WORDS_PER_SECTOR
    assert n_lines * isa.WORDS_PER_SECTOR == mem_words, mem_words

    def kernel(program_ref, init_pc_ref, init_regs_ref, init_mem_ref,
               n_active_ref, seed_ref, horizon_ref, max_events_ref,
               costs_ref, wa_base_ref, wa_mask_ref, wa_size_ref,
               *rest):
        """One grid step = one sweep cell, start to finish.

        Refs hold this cell's (1, ...) blocks; indexing row 0 materializes
        the cell's state in kernel memory, where the whole event burst runs
        before the final stats are stored back.  ``rest`` is the four fault
        refs (when ``n_faults > 0``) followed by the eight output refs.
        """
        fault_refs, out_refs = rest[:-8], rest[-8:]
        (acq_ref, wacq_ref, hs_ref, hc_ref, ev_ref, slp_ref, mem_ref,
         lh_ref) = out_refs
        fault_fields = {}
        if fault_refs:
            fault_fields = dict(zip(
                ("f_kind", "f_evt", "f_tid", "f_arg"),
                (r[0] for r in fault_refs)))
        c = SimConsts(program=program_ref[0], costs=costs_ref[0],
                      wa_base=wa_base_ref[0], wa_mask=wa_mask_ref[0],
                      wa_size=wa_size_ref[0], horizon=horizon_ref[0],
                      max_events=max_events_ref[0], **fault_fields)
        s0 = _initial_state(n_threads, mem_words, n_locks,
                            init_pc_ref[0], init_regs_ref[0],
                            init_mem_ref[0], n_active_ref[0], seed_ref[0])

        def live(s):
            # exactly the single-cell driver's loop condition
            t_th = jnp.min(s.next_time)
            t_cm = jnp.min(jnp.where(s.pend_addr >= 0, s.pend_time, INF))
            return (s.events < c.max_events) & \
                (jnp.minimum(t_th, t_cm) < c.horizon)

        def burst(s):
            return jax.lax.fori_loop(0, chunk, lambda _, st: _step(c, st), s)

        s = jax.lax.while_loop(live, burst, s0)
        acq_ref[0] = s.acq
        wacq_ref[0] = s.waited_acq
        hs_ref[0] = s.hand_sum
        hc_ref[0] = s.hand_cnt
        ev_ref[0] = s.events
        slp_ref[0] = (s.spin_addr >= 0).sum().astype(jnp.int32)
        mem_ref[0] = s.mem
        lh_ref[0] = s.lat_hist

    def run(program, init_pc, init_regs, init_mem, n_active, seed,
            horizon, max_events, costs, wa_base, wa_mask, wa_size, *faults):
        assert len(faults) == (4 if n_faults else 0), \
            (len(faults), n_faults)
        n_cells = program.shape[0]
        cell1 = lambda i: (i,)          # noqa: E731 - tiny index maps
        cell2 = lambda i: (i, 0)        # noqa: E731
        cell3 = lambda i: (i, 0, 0)     # noqa: E731
        scalar = pl.BlockSpec((1,), cell1)
        i32 = jnp.int32
        out = pl.pallas_call(
            kernel,
            grid=(n_cells,),
            in_specs=[
                pl.BlockSpec((1, prog_len, 5), cell3),     # program
                pl.BlockSpec((1, n_threads), cell2),       # init_pc
                pl.BlockSpec((1, n_threads, isa.N_REGS), cell3),  # init_regs
                pl.BlockSpec((1, mem_words), cell2),       # init_mem
                scalar, scalar, scalar, scalar,            # n_active, seed,
                #                                            horizon, max_ev
                pl.BlockSpec((1, 9), cell2),               # costs
                scalar, scalar, scalar,                    # wa_base/mask/size
            ] + [pl.BlockSpec((1, n_faults), cell2)] * len(faults),
            out_specs=[
                pl.BlockSpec((1, n_threads), cell2),       # acquisitions
                pl.BlockSpec((1, n_threads), cell2),       # waited
                scalar, scalar, scalar, scalar,            # hand_sum/cnt,
                #                                            events, sleeping
                pl.BlockSpec((1, mem_words), cell2),       # grant_value
                pl.BlockSpec((1, N_LAT_BUCKETS), cell2),   # lat_hist
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_cells, n_threads), i32),
                jax.ShapeDtypeStruct((n_cells, n_threads), i32),
                jax.ShapeDtypeStruct((n_cells,), i32),
                jax.ShapeDtypeStruct((n_cells,), i32),
                jax.ShapeDtypeStruct((n_cells,), i32),
                jax.ShapeDtypeStruct((n_cells,), i32),
                jax.ShapeDtypeStruct((n_cells, mem_words), i32),
                jax.ShapeDtypeStruct((n_cells, N_LAT_BUCKETS), i32),
            ],
            interpret=interpret,
        )(program, init_pc, init_regs, init_mem, n_active, seed,
          horizon, max_events, costs, wa_base, wa_mask, wa_size, *faults)
        return dict(zip(OUT_KEYS, out))

    return run
