"""lockVM engine — jitted event-driven execution under a coherence cost model.

Sequentially-consistent interleaving: a global virtual clock, one event per
step.  Each thread owns an independent timeline (``next_time``); costs charge
the *issuing* thread, so unrelated memory operations proceed in parallel —
except that a store's visibility is delayed by its coherence cost (pending
commit), which is precisely how the invalidation diameter retards handover.

Event kinds:
  * thread op  — fetch program[pc[t]], dispatch via lax.switch.
  * commit     — a delayed store becomes globally visible: memory updated,
                 sharers invalidated, spinners watching the line woken
                 (they pay the refill miss and re-evaluate their condition).

RMWs (FADD/SWAP/CASZ) apply immediately (the coherence controller serializes
them) but charge full cost and wake watchers.  Loads register the thread as a
line sharer; SPIN sleepers stay registered while parked — so every release
store pays C_INV × (#threads camped on that line): ticket locks pay O(T),
TWA pays O(LongTermThreshold). That asymmetry is the paper.

Sharer bitsets: the per-line sharer set is a packed ``(n_lines,
ceil(T/32)) uint32`` bitset, not a ``(n_lines, T)`` bool matrix.  Thread
``t`` owns bit ``t & 31`` of word ``t >> 5``; ``store_cost``'s invalidation
count is a popcount over the line's words, sharer registration ORs one bit
into one word, and an exclusive grab (RMW / commit) rewrites the whole row
to the actor's lone bit.  This shrinks the hot per-step state 32× — the
paper's compact-waiting-state argument, applied to the simulator itself.

Structure (batched-sweep refactor):
  * :func:`_step` — pure single-event transition ``(SimConsts, SimState) ->
    SimState``.  Event selection is ONE fused argmin over the concatenated
    ``[pending-commit times | thread times]`` vector (ties resolve to the
    commit, matching the historical ``t_cm <= t_th`` rule).  The opcode
    switch computes only a compact :class:`Effects` record (scalars plus one
    register row); the big-array updates (memory, sharer bitsets, pending
    stores, wakeups) are applied ONCE outside the switch.  This matters
    under ``vmap``: a batched ``lax.switch`` executes every branch and
    selects, so branches must not carry whole-state copies.  A store commit
    is dispatched through the same switch as pseudo-opcode ``isa.N_OPS``.
  * :func:`_make_run` — wraps the step in a ``lax.while_loop`` driver plus
    stats extraction.
  * :func:`_build_engine` — lru-cached jit of the driver, keyed ONLY on array
    shapes ``(n_threads, mem_words, n_locks, prog_len)`` (plus the lane
    geometry for the scheduler).  Everything else — program contents, costs,
    waiting-array geometry, horizon — is a traced input, so sweeping any of
    those axes reuses one executable.
  * :func:`run_sweep` — batched sweep in ONE compiled call, four drivers:
    ``mode="vmap"`` (lane-parallel, every cell is a lane), ``mode="map"``
    (sequential cells), ``mode="sched"`` — a chunked work-stealing lane
    scheduler (:func:`_make_run_sched`): ``lanes`` lanes step in fixed-size
    chunks inside an outer while loop, and a lane whose cell finished is
    refilled from the queue of not-yet-started cells.  A skewed sweep then
    costs ~``sum(events)`` lane-steps instead of vmap's ``max(events) × B``,
    while per-cell results stay bit-identical to ``mode="map"`` (each cell
    still executes its private event sequence — only lane placement
    changes).  ``mode="pallas"`` (:mod:`repro.sim.engine_pallas`) fuses the
    whole per-cell event loop into one Pallas kernel grid step — hot state
    resident in kernel memory across a ``chunk``-event burst instead of a
    per-event ``lax.while_loop`` carry; it runs in interpret mode on CPU and
    does not lower for TPU yet (see :data:`repro.sim.engine_pallas.
    TPU_REFUSAL`).  ``mode="auto"`` (:func:`choose_mode`) picks a driver
    from the backend kind plus the sweep shape.  Cells with fewer threads
    than the batch maximum mask the excess threads inactive (``next_time =
    INF`` forever), which leaves their per-event behaviour bit-identical to
    an unpadded run.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import isa
from .costs import (DEFAULT_COSTS, I_ATOMIC, I_HIT, I_INV, I_LOCAL, I_MISS,
                    I_ST_OWNED, I_ST_SHARED, I_WAKE, I_XFER, Costs)
from .faults import F_ABORT, F_PREEMPT, F_SPURIOUS, FaultSchedule
from .programs import PROG_LEN, pad_program

INF = np.int32(1 << 29)

# Acquire-latency histogram geometry: bucket k counts samples with
# ``lat >= 2^(k-1)`` and ``lat < 2^k`` (bucket 0 = zero-latency, bucket 31 =
# everything from 2^30 up).  The bucket index is the number of powers of two
# at or below the sample — ``sum(lat >= 2^k for k in 0..30)`` — computed with
# the same formula in the engine, the NumPy oracles and the C kernel.
N_LAT_BUCKETS = 32

log = logging.getLogger(__name__)

# The deterministic event-order contract, shared verbatim with the pure-NumPy
# reference interpreter (``repro.sim.check.oracle``).  Any change to event
# selection in :func:`_step` MUST update this string and the oracle together —
# the differential fuzzer asserts bit-identical stats, so even a tie-break
# flip is a detectable (and intended-to-be-detected) divergence.
EVENT_ORDER_CONTRACT = (
    "one fused argmin over the concatenated [pending-commit times | thread "
    "times] vector, first-minimum wins: a commit/thread-op tie resolves to "
    "the commit, ties within a half resolve to the lowest thread index; "
    "store commits fire at issue_time + store_cost, woken spinners resume "
    "at wake_time + C_WAKE + wake_delay (clearing wake_delay) and re-pay "
    "the refill load on re-execution; when a fault schedule is present, "
    "entries whose event index equals the current event counter are applied "
    "as persisted state mutations BEFORE event selection, gated on the "
    "pre-fault state being live (events < max_events and earliest pre-fault "
    "event time < horizon): a preemption adds K to a running thread's "
    "next_time, or accumulates K into a parked/halted thread's wake_delay; "
    "a spurious wake resumes a parked thread at pre-fault t_min + C_WAKE + "
    "wake_delay (clearing wake_delay and spin_addr, pc unchanged); an abort "
    "sets next_time = INF and spin_addr = -1 (never wakeable); pending "
    "stores are never touched by faults; the event then selects from the "
    "post-fault state — if no post-fault event time is below the horizon, "
    "no event executes and the event counter does not advance"
)


def bitset_words(n_threads: int) -> int:
    """Words in a packed per-line sharer bitset (32 threads per uint32)."""
    return (n_threads + 31) // 32


class SimConsts(NamedTuple):
    """Per-run inputs that stay fixed for the whole simulation (all traced)."""

    program: jax.Array     # (prog_len, 5) int32 micro-ops
    costs: jax.Array       # (9,) int32 — Costs.to_array()
    wa_base: jax.Array     # () int32 waiting-array base address
    wa_mask: jax.Array     # () int32 index mask (wa_size - 1)
    wa_size: jax.Array     # () int32 per-lock array stride (HASHP)
    horizon: jax.Array     # () int32 stop once every timeline passes this
    max_events: jax.Array  # () int32 hard event-count bound
    # Optional fault schedule (see repro.sim.faults); None = fault-free, and
    # None-ness is a Python-level pytree property, so the zero-fault compiled
    # step contains no fault code at all.
    f_kind: jax.Array | None = None  # (n_faults,) int32 fault kind, 0 = pad
    f_evt: jax.Array | None = None   # (n_faults,) int32 global event index
    f_tid: jax.Array | None = None   # (n_faults,) int32 target thread
    f_arg: jax.Array | None = None   # (n_faults,) int32 preemption window K


class SimState(NamedTuple):
    """Full simulator state; a pytree so it threads through lax.while_loop."""

    next_time: jax.Array   # (T,) per-thread timeline; INF = parked/inactive
    pc: jax.Array          # (T,)
    regs: jax.Array        # (T, N_REGS)
    prng: jax.Array        # (T,) uint32 LCG state
    mem: jax.Array         # (mem_words,)
    sharers: jax.Array     # (n_lines, ceil(T/32)) uint32 bitset — cached lines
    dirty: jax.Array       # (n_lines,) owning thread or -1
    pend_addr: jax.Array   # (T,) pending-store address or -1
    pend_val: jax.Array    # (T,)
    pend_time: jax.Array   # (T,) commit time of the pending store
    spin_addr: jax.Array   # (T,) watched address while parked, or -1
    wake_delay: jax.Array  # (T,) preemption debt paid at the next wake
    acq: jax.Array         # (T,) lock acquisitions
    waited_acq: jax.Array  # (T,) acquisitions that had to wait
    rel_time: jax.Array    # (n_locks,) last REL timestamp or -1
    hand_sum: jax.Array    # () summed handover latency
    hand_cnt: jax.Array    # () handovers measured
    events: jax.Array      # () total events executed
    acq_t0: jax.Array      # (T,) TSTART mark (acquire began at), -1 = unset
    lat_hist: jax.Array    # (N_LAT_BUCKETS,) log2 acquire-latency histogram


class Effects(NamedTuple):
    """What one event does, in O(1) scalars plus the actor's register row.

    Every switch branch returns one of these; the apply phase in
    :func:`_step` turns it into state updates.  "actor" is the executing
    thread for a program op, or the committing thread for a store commit.
    Sentinel -1 disables an address/index-valued effect.
    """

    cost: jax.Array        # charged to the actor (advancing events only)
    new_pc: jax.Array
    reg_row: jax.Array     # (N_REGS,) the actor's registers after the event
    prng_t: jax.Array      # actor's PRNG state after the event
    sleep: jax.Array       # bool — park the actor (next_time = INF)
    advance: jax.Array     # bool — update the actor's pc/regs/prng/next_time
    st_addr: jax.Array     # delayed-store address, -1 = none
    st_val: jax.Array
    st_time: jax.Array     # commit time of the delayed store
    clear_pend: jax.Array  # bool — a commit consumed the actor's pending store
    w_addr: jax.Array      # immediate memory write (RMW/commit), -1 = none
    w_val: jax.Array
    excl_ln: jax.Array     # line that became exclusive to the actor, -1 = none
    share_ln: jax.Array    # line the actor registered as a sharer of, -1
    downgrade: jax.Array   # bool — dirty[share_ln] = -1 (foreign dirty read)
    park_addr: jax.Array   # actor parks watching this address, -1 = none
    wake_addr: jax.Array   # wake watchers of this address, -1 = none
    wake_time: jax.Array
    acq_inc: jax.Array     # bool — actor completed an acquisition
    waited_inc: jax.Array  # bool — ... that had to wait
    hand_add: jax.Array    # handover latency to accumulate
    hand_inc: jax.Array    # bool
    rel_idx: jax.Array     # rel_time slot to write, -1 = none
    rel_val: jax.Array
    t0_new: jax.Array      # actor's acq_t0 after the event, -2 = keep
    lat_idx: jax.Array     # latency-histogram bucket to bump, -1 = none


def _event_times(s: SimState):
    """Earliest thread-op time and earliest pending-commit time."""
    t_th = jnp.min(s.next_time)
    t_cm = jnp.min(jnp.where(s.pend_addr >= 0, s.pend_time, INF))
    return t_th, t_cm


def _step(c: SimConsts, s: SimState) -> SimState:
    """Advance the simulation by exactly one event (commit or thread op)."""
    n_threads = s.next_time.shape[0]
    C = c.costs

    (next_time, pc, regs, prng, mem, sharers, dirty,
     pend_addr, pend_val, pend_time, spin_addr, wake_delay,
     acq, waited_acq, rel_time, hand_sum, hand_cnt, events,
     acq_t0, lat_hist) = s

    # ---- fault phase (statically absent when no schedule is attached) ----
    # Entries matching the current event counter mutate the thread timelines
    # BEFORE event selection, gated on the PRE-fault state being live — a
    # finished/stalled lane never advances ``events``, so its remaining
    # schedule can never fire (and the no-event identity is preserved for
    # the batched drivers' overshoot steps).  Schedules carry unique event
    # indices, so at most one entry applies per step and scatter order is
    # irrelevant.  Post-fault, the normal selection below runs: if the fault
    # pushed every timeline past the horizon, the step dispatches no-event
    # and the counter stays put (the mutations themselves persist).
    fault_on = c.f_kind is not None
    if fault_on:
        ptimes0 = jnp.where(pend_addr >= 0, pend_time, INF)
        pre_min = jnp.minimum(jnp.min(ptimes0), jnp.min(next_time))
        flive = (events < c.max_events) & (pre_min < c.horizon)
        hit = flive & (c.f_kind != 0) & (c.f_evt == events)
        running = next_time < INF
        # preemption: a running thread's timeline slips K; a parked/halted
        # thread instead owes K at its next wake (wake_delay)
        k_add = jnp.zeros(n_threads, jnp.int32).at[c.f_tid].add(
            jnp.where(hit & (c.f_kind == F_PREEMPT), c.f_arg, 0))
        next_time = next_time + jnp.where(running, k_add, 0)
        wake_delay = wake_delay + jnp.where(running, 0, k_add)
        # spurious wake: a parked thread resumes (pc still on the SPIN op)
        spur = jnp.zeros(n_threads, jnp.int32).at[c.f_tid].add(
            (hit & (c.f_kind == F_SPURIOUS)).astype(jnp.int32)) > 0
        spur = spur & (spin_addr >= 0)
        next_time = jnp.where(spur, pre_min + C[I_WAKE] + wake_delay,
                              next_time)
        wake_delay = jnp.where(spur, 0, wake_delay)
        spin_addr = jnp.where(spur, -1, spin_addr)
        # abort: dead forever — not parked (spin_addr = -1), never woken
        dead = jnp.zeros(n_threads, jnp.int32).at[c.f_tid].add(
            (hit & (c.f_kind == F_ABORT)).astype(jnp.int32)) > 0
        next_time = jnp.where(dead, INF, next_time)
        spin_addr = jnp.where(dead, -1, spin_addr)

    # One fused reduction picks the next event: argmin over the concatenated
    # [pending-commit times | thread times] vector.  A tie between the two
    # halves lands in the commit half (first occurrence), preserving the
    # historical ``t_cm <= t_th`` commit-wins rule bit for bit.
    ptimes = jnp.where(pend_addr >= 0, pend_time, INF)
    k = jnp.argmin(jnp.concatenate([ptimes, next_time])).astype(jnp.int32)
    is_commit = k < n_threads
    tc = jnp.minimum(k, n_threads - 1)          # commit thread (dead if op)
    t = jnp.where(is_commit, 0, k - n_threads)  # op thread (dead if commit)
    t_min = jnp.where(is_commit, ptimes[tc], next_time[t])
    # Self-guarding: a lane past its horizon / event budget dispatches the
    # no-event pseudo-op, making the whole step an identity.  The unbatched
    # driver's loop condition never lets this fire; the batched drivers rely
    # on it so lanes that finish early idle for free (no per-lane select).
    live = (events < c.max_events) & (t_min < c.horizon)

    now = t_min
    instr = c.program[pc[t]]
    op, a, b, cc, imm = instr[0], instr[1], instr[2], instr[3], instr[4]
    ra, rb, rc = regs[t, a], regs[t, b], regs[t, cc]
    pc1 = pc[t] + 1
    t_bit = jnp.uint32(1) << (t & 31).astype(jnp.uint32)
    t_word = t >> 5

    def load_cost(ln):
        mine = (sharers[ln, t_word] & t_bit) > 0
        d = dirty[ln]
        return jnp.where(mine, C[I_HIT],
                         jnp.where((d >= 0) & (d != t), C[I_XFER], C[I_MISS]))

    def store_cost(ln, atomic):
        row = sharers[ln]
        total = jax.lax.population_count(row).sum().astype(jnp.int32)
        mine = ((row[t_word] & t_bit) > 0).astype(jnp.int32)
        others = total - mine
        only = (mine > 0) & (others == 0)
        cost = jnp.where(only, C[I_ST_OWNED], C[I_ST_SHARED] + C[I_INV] * others)
        return (cost + jnp.where(atomic, C[I_ATOMIC], 0)).astype(jnp.int32)

    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    none = i32(-1)
    zero = i32(0)
    no = jnp.zeros((), bool)
    yes = jnp.ones((), bool)
    default = Effects(
        cost=C[I_LOCAL], new_pc=pc1, reg_row=regs[t], prng_t=prng[t],
        sleep=no, advance=yes,
        st_addr=none, st_val=zero, st_time=zero, clear_pend=no,
        w_addr=none, w_val=zero, excl_ln=none,
        share_ln=none, downgrade=no, park_addr=none,
        wake_addr=none, wake_time=zero,
        acq_inc=no, waited_inc=no, hand_add=zero, hand_inc=no,
        rel_idx=none, rel_val=zero, t0_new=i32(-2), lat_idx=none)

    def h_nop():
        return default

    def h_load():
        addr = rb + imm
        ln = addr >> isa.LINE_SHIFT
        mine = (sharers[ln, t_word] & t_bit) > 0
        d = dirty[ln]
        return default._replace(
            cost=load_cost(ln),
            reg_row=regs[t].at[a].set(mem[addr]),
            share_ln=ln,
            downgrade=(~mine) & (d >= 0) & (d != t))

    def _store(addr, val):
        ln = addr >> isa.LINE_SHIFT
        cost = store_cost(ln, False)
        return default._replace(cost=cost, st_addr=addr, st_val=val,
                                st_time=now + cost)

    def h_store():
        return _store(ra + imm, rb)

    def h_storei():
        return _store(ra + imm, b)

    def _rmw(addr, new_val, dst_old):
        """Immediate atomic RMW: apply, invalidate, wake watchers."""
        ln = addr >> isa.LINE_SHIFT
        cost = store_cost(ln, True)
        old = mem[addr]
        return default._replace(
            cost=cost,
            reg_row=regs[t].at[dst_old].set(old),
            w_addr=addr, w_val=i32(new_val(old)),
            excl_ln=ln, wake_addr=addr, wake_time=now + cost)

    def h_fadd():
        return _rmw(rb + imm, lambda old: old + cc, a)

    def h_swap():
        return _rmw(rb + imm, lambda old: rc, a)

    def h_casz():
        return _rmw(rb + imm, lambda old: jnp.where(old == rc, 0, old), a)

    def _alu(value):
        return default._replace(reg_row=regs[t].at[a].set(value))

    def h_addi():
        return _alu(rb + imm)

    def h_movi():
        return _alu(imm)

    def h_mov():
        return _alu(rb)

    def h_sub():
        return _alu(rb - rc)

    def h_muli():
        return _alu(rb * imm)

    def h_andi():
        return _alu(rb & imm)

    def h_hash():
        return _alu(c.wa_base + (((rb * 127) ^ rc) & c.wa_mask))

    def h_hashp():
        return _alu(c.wa_base + rc * c.wa_size + ((rb * 127) & c.wa_mask))

    def _branch(cond):
        return default._replace(new_pc=i32(jnp.where(cond, imm, pc1)))

    def h_beq():
        return _branch(ra == rb)

    def h_bne():
        return _branch(ra != rb)

    def h_ble():
        return _branch(ra <= rb)

    def h_bgt():
        return _branch(ra > rb)

    def h_beqi():
        return _branch(ra == cc)

    def h_bnei():
        return _branch(ra != cc)

    def h_blei():
        return _branch(ra <= cc)

    def h_bgti():
        return _branch(ra > cc)

    def h_jmp():
        return _branch(True)

    def h_worki():
        return default._replace(cost=jnp.maximum(imm, 1))

    def h_workr():
        return default._replace(cost=jnp.maximum(ra, 1))

    def h_prng():
        sd = prng[t] * jnp.uint32(1664525) + jnp.uint32(1013904223)
        val = ((sd >> jnp.uint32(16)).astype(jnp.int32)) % jnp.maximum(imm, 1)
        return default._replace(reg_row=regs[t].at[a].set(val), prng_t=sd)

    def _spin(proceed, addr):
        """Fused spin: proceed (load cost) or park camped on the line."""
        ln = addr >> isa.LINE_SHIFT
        return default._replace(
            cost=load_cost(ln),
            new_pc=i32(jnp.where(proceed, pc1, pc[t])),
            share_ln=ln,
            sleep=~proceed,
            park_addr=i32(jnp.where(proceed, -1, addr)))

    def h_spin_eq():
        addr = rb + imm
        return _spin(mem[addr] == ra, addr)

    def h_spin_ne():
        addr = rb + imm
        return _spin(mem[addr] != ra, addr)

    def h_spin_eqi():
        addr = rb + imm
        return _spin(mem[addr] == cc, addr)

    def h_spin_nei():
        addr = rb + imm
        return _spin(mem[addr] != cc, addr)

    def h_spin_ge():
        # Wrap-safe frontier compare: the sign of the int32 DIFFERENCE, not
        # a direct >=.  Tickets/grants are free-running int32 counters, so
        # once they cross INT32_MAX the grant is a huge negative while a
        # pre-wrap ticket frontier is a huge positive — `mem >= ra` would
        # park the waiter forever even though the frontier has passed it.
        addr = rb + imm
        return _spin(mem[addr] - ra >= 0, addr)

    def h_acq():
        lidx = ra
        rt = rel_time[lidx]
        waited = cc > 0
        got = waited & (rt >= 0)
        # acquire latency: a pending TSTART mark is consumed into the log2
        # histogram (marks survive aborted attempts until the next ACQ, so
        # redraw loops measure from the FIRST attempt)
        t0v = acq_t0[t]
        marked = t0v >= 0
        blat = jnp.maximum(now - t0v, 0)
        bucket = (blat >= (i32(1) << jnp.arange(N_LAT_BUCKETS - 1,
                                                dtype=jnp.int32))
                  ).sum().astype(jnp.int32)
        return default._replace(
            acq_inc=yes, waited_inc=waited,
            hand_add=i32(jnp.where(got, now - rt, 0)), hand_inc=got,
            rel_idx=lidx, rel_val=i32(jnp.where(got, -1, rt)),
            lat_idx=i32(jnp.where(marked, bucket, -1)),
            t0_new=i32(jnp.where(marked, -1, -2)))

    def h_tstart():
        return default._replace(t0_new=now)

    def h_rel():
        return default._replace(rel_idx=rb, rel_val=now)

    def h_halt():
        return default._replace(cost=i32(INF), new_pc=pc[t])

    def h_commit():
        """Pseudo-op: the earliest pending store becomes globally visible."""
        addr = pend_addr[tc]
        ln = addr >> isa.LINE_SHIFT
        return default._replace(
            advance=no, clear_pend=yes,
            w_addr=addr, w_val=pend_val[tc],
            excl_ln=ln, wake_addr=addr, wake_time=t_min)

    def h_noevent():
        """Pseudo-op for finished lanes: touch nothing."""
        return default._replace(advance=no)

    handlers = [None] * isa.N_OPS
    handlers[isa.NOP] = h_nop
    handlers[isa.LOAD] = h_load
    handlers[isa.STORE] = h_store
    handlers[isa.STOREI] = h_storei
    handlers[isa.FADD] = h_fadd
    handlers[isa.SWAP] = h_swap
    handlers[isa.CASZ] = h_casz
    handlers[isa.ADDI] = h_addi
    handlers[isa.MOVI] = h_movi
    handlers[isa.MOV] = h_mov
    handlers[isa.SUB] = h_sub
    handlers[isa.MULI] = h_muli
    handlers[isa.ANDI] = h_andi
    handlers[isa.HASH] = h_hash
    handlers[isa.HASHP] = h_hashp
    handlers[isa.BEQ] = h_beq
    handlers[isa.BNE] = h_bne
    handlers[isa.BLE] = h_ble
    handlers[isa.BGT] = h_bgt
    handlers[isa.BEQI] = h_beqi
    handlers[isa.BNEI] = h_bnei
    handlers[isa.BLEI] = h_blei
    handlers[isa.BGTI] = h_bgti
    handlers[isa.JMP] = h_jmp
    handlers[isa.WORKI] = h_worki
    handlers[isa.WORKR] = h_workr
    handlers[isa.PRNG] = h_prng
    handlers[isa.SPIN_EQ] = h_spin_eq
    handlers[isa.SPIN_NE] = h_spin_ne
    handlers[isa.SPIN_EQI] = h_spin_eqi
    handlers[isa.SPIN_NEI] = h_spin_nei
    handlers[isa.ACQ] = h_acq
    handlers[isa.REL] = h_rel
    handlers[isa.HALT] = h_halt
    handlers[isa.SPIN_GE] = h_spin_ge
    handlers[isa.TSTART] = h_tstart
    handlers.append(h_commit)   # pseudo-opcode isa.N_OPS
    handlers.append(h_noevent)  # pseudo-opcode isa.N_OPS + 1

    branch = jnp.where(live, jnp.where(is_commit, isa.N_OPS, op),
                       isa.N_OPS + 1)
    e: Effects = jax.lax.switch(branch, handlers)

    # ---- apply phase: every state update happens exactly once ------------
    actor = jnp.where(is_commit, tc, t)
    adv = e.advance

    # wake watchers of the written line (commit / RMW); a woken thread pays
    # any preemption debt accrued while parked on top of C_WAKE
    wake = (e.wake_addr >= 0) & (spin_addr == e.wake_addr)
    if fault_on:
        nt2 = jnp.where(wake, e.wake_time + C[I_WAKE] + wake_delay, next_time)
        wd2 = jnp.where(wake, 0, wake_delay)
    else:
        nt2 = jnp.where(wake, e.wake_time + C[I_WAKE], next_time)
        wd2 = wake_delay
    sp2 = jnp.where(wake, -1, spin_addr)
    # actor park / advance (the actor's own update wins over a wake)
    sp2 = sp2.at[actor].set(jnp.where(e.park_addr >= 0, e.park_addr,
                                      sp2[actor]))
    nt2 = nt2.at[actor].set(jnp.where(
        adv, jnp.where(e.sleep, INF, now + e.cost), nt2[actor]))

    pc2 = pc.at[actor].set(jnp.where(adv, e.new_pc, pc[actor]))
    regs2 = regs.at[actor].set(jnp.where(adv, e.reg_row, regs[actor]))
    prng2 = prng.at[actor].set(jnp.where(adv, e.prng_t, prng[actor]))

    # immediate memory write (RMW / commit)
    wa = jnp.where(e.w_addr >= 0, e.w_addr, 0)
    mem2 = mem.at[wa].set(jnp.where(e.w_addr >= 0, e.w_val, mem[wa]))

    # sharer registration (+ downgrade of a foreign dirty line): OR the
    # actor's bit into its bitset word
    a_bit = jnp.uint32(1) << (actor & 31).astype(jnp.uint32)
    a_word = actor >> 5
    ls = jnp.where(e.share_ln >= 0, e.share_ln, 0)
    sh2 = sharers.at[ls, a_word].set(jnp.where(
        e.share_ln >= 0, sharers[ls, a_word] | a_bit, sharers[ls, a_word]))
    dr2 = dirty.at[ls].set(jnp.where((e.share_ln >= 0) & e.downgrade,
                                     -1, dirty[ls]))
    # exclusive ownership (RMW / commit): invalidate every other sharer —
    # the whole row collapses to the actor's lone bit
    n_words = sharers.shape[1]
    le = jnp.where(e.excl_ln >= 0, e.excl_ln, 0)
    lone = jnp.where(jnp.arange(n_words) == a_word, a_bit, jnp.uint32(0))
    sh2 = sh2.at[le].set(jnp.where(e.excl_ln >= 0, lone, sh2[le]))
    dr2 = dr2.at[le].set(jnp.where(e.excl_ln >= 0, actor, dr2[le]))

    # pending-store queue (enqueue on STORE/STOREI, consume on commit)
    pa2 = pend_addr.at[actor].set(jnp.where(
        e.st_addr >= 0, e.st_addr,
        jnp.where(e.clear_pend, -1, pend_addr[actor])))
    pv2 = pend_val.at[actor].set(jnp.where(e.st_addr >= 0, e.st_val,
                                           pend_val[actor]))
    pt2 = pend_time.at[actor].set(jnp.where(e.st_addr >= 0, e.st_time,
                                            pend_time[actor]))

    # lock bookkeeping
    acq2 = acq.at[actor].add(e.acq_inc.astype(jnp.int32))
    wacq2 = waited_acq.at[actor].add(e.waited_inc.astype(jnp.int32))
    ri = jnp.where(e.rel_idx >= 0, e.rel_idx, 0)
    rel2 = rel_time.at[ri].set(jnp.where(e.rel_idx >= 0, e.rel_val,
                                         rel_time[ri]))
    hs2 = hand_sum + e.hand_add
    hc2 = hand_cnt + e.hand_inc.astype(jnp.int32)

    # acquire-latency mark + log2 histogram
    t02 = acq_t0.at[actor].set(jnp.where(e.t0_new != -2, e.t0_new,
                                         acq_t0[actor]))
    li = jnp.where(e.lat_idx >= 0, e.lat_idx, 0)
    lh2 = lat_hist.at[li].add((e.lat_idx >= 0).astype(jnp.int32))

    return SimState(nt2, pc2, regs2, prng2, mem2, sh2, dr2,
                    pa2, pv2, pt2, sp2, wd2,
                    acq2, wacq2, rel2, hs2, hc2,
                    events + live.astype(jnp.int32), t02, lh2)


def _initial_state(n_threads: int, mem_words: int, n_locks: int,
                   init_pc, init_regs, init_mem, n_active, seed) -> SimState:
    n_lines = mem_words // isa.WORDS_PER_SECTOR
    active = jnp.arange(n_threads) < n_active
    return SimState(
        next_time=jnp.where(active, 0, INF).astype(jnp.int32),
        pc=init_pc.astype(jnp.int32),
        regs=init_regs.astype(jnp.int32),
        prng=(seed.astype(jnp.uint32)
              + jnp.arange(n_threads, dtype=jnp.uint32) * jnp.uint32(2654435761)),
        mem=init_mem.astype(jnp.int32),
        sharers=jnp.zeros((n_lines, bitset_words(n_threads)), jnp.uint32),
        dirty=jnp.full(n_lines, -1, jnp.int32),
        pend_addr=jnp.full(n_threads, -1, jnp.int32),
        pend_val=jnp.zeros(n_threads, jnp.int32),
        pend_time=jnp.zeros(n_threads, jnp.int32),
        spin_addr=jnp.full(n_threads, -1, jnp.int32),
        wake_delay=jnp.zeros(n_threads, jnp.int32),
        acq=jnp.zeros(n_threads, jnp.int32),
        waited_acq=jnp.zeros(n_threads, jnp.int32),
        rel_time=jnp.full(n_locks, -1, jnp.int32),
        hand_sum=jnp.zeros((), jnp.int32),
        hand_cnt=jnp.zeros((), jnp.int32),
        events=jnp.zeros((), jnp.int32),
        acq_t0=jnp.full(n_threads, -1, jnp.int32),
        lat_hist=jnp.zeros(N_LAT_BUCKETS, jnp.int32),
    )


def _fault_fields(faults) -> dict:
    """kwargs for SimConsts from a 0- or 4-tuple of fault arrays."""
    if not faults:
        return {}
    assert len(faults) == 4, len(faults)
    return dict(zip(("f_kind", "f_evt", "f_tid", "f_arg"), faults))


def _make_run(n_threads: int, mem_words: int, n_locks: int):
    """While-loop driver over the single-event step for one shape set."""

    def run(program, init_pc, init_regs, init_mem, n_active, seed,
            horizon, max_events, costs, wa_base, wa_mask, wa_size, *faults):
        c = SimConsts(program=program, costs=costs,
                      wa_base=wa_base, wa_mask=wa_mask, wa_size=wa_size,
                      horizon=horizon, max_events=max_events,
                      **_fault_fields(faults))

        def cond(s: SimState):
            t_th, t_cm = _event_times(s)
            return (s.events < c.max_events) & (jnp.minimum(t_th, t_cm) < c.horizon)

        final = jax.lax.while_loop(cond, functools.partial(_step, c),
                                   _initial_state(n_threads, mem_words, n_locks,
                                                  init_pc, init_regs, init_mem,
                                                  n_active, seed))
        return {
            "acquisitions": final.acq,
            "waited_acquisitions": final.waited_acq,
            "handover_sum": final.hand_sum,
            "handover_count": final.hand_cnt,
            "events": final.events,
            "sleeping": (final.spin_addr >= 0).sum(),
            "grant_value": final.mem,  # full memory; callers slice what they need
            "lat_hist": final.lat_hist,
        }

    return run


def _make_run_batched(n_threads: int, mem_words: int, n_locks: int):
    """Batched driver: ONE while loop over a ``jax.vmap`` of the step.

    Running ``vmap`` *inside* the loop (rather than vmapping the whole
    single-cell driver) avoids the per-lane full-state select a batched
    ``lax.while_loop`` would otherwise emit every iteration: the step is
    self-guarding (finished lanes dispatch the no-event pseudo-op and are
    exact identities), so the loop simply runs until every lane is done.
    """
    n_lines = mem_words // isa.WORDS_PER_SECTOR

    def run(program, init_pc, init_regs, init_mem, n_active, seed,
            horizon, max_events, costs, wa_base, wa_mask, wa_size, *faults):
        n_cells = program.shape[0]
        c = SimConsts(program=program, costs=costs,
                      wa_base=wa_base, wa_mask=wa_mask, wa_size=wa_size,
                      horizon=horizon, max_events=max_events,
                      **_fault_fields(faults))
        lane_t = jnp.arange(n_threads)[None, :]
        s0 = SimState(
            next_time=jnp.where(lane_t < n_active[:, None], 0, INF
                                ).astype(jnp.int32),
            pc=init_pc.astype(jnp.int32),
            regs=init_regs.astype(jnp.int32),
            prng=(seed[:, None].astype(jnp.uint32)
                  + lane_t.astype(jnp.uint32) * jnp.uint32(2654435761)),
            mem=init_mem.astype(jnp.int32),
            sharers=jnp.zeros((n_cells, n_lines, bitset_words(n_threads)),
                              jnp.uint32),
            dirty=jnp.full((n_cells, n_lines), -1, jnp.int32),
            pend_addr=jnp.full((n_cells, n_threads), -1, jnp.int32),
            pend_val=jnp.zeros((n_cells, n_threads), jnp.int32),
            pend_time=jnp.zeros((n_cells, n_threads), jnp.int32),
            spin_addr=jnp.full((n_cells, n_threads), -1, jnp.int32),
            wake_delay=jnp.zeros((n_cells, n_threads), jnp.int32),
            acq=jnp.zeros((n_cells, n_threads), jnp.int32),
            waited_acq=jnp.zeros((n_cells, n_threads), jnp.int32),
            rel_time=jnp.full((n_cells, n_locks), -1, jnp.int32),
            hand_sum=jnp.zeros(n_cells, jnp.int32),
            hand_cnt=jnp.zeros(n_cells, jnp.int32),
            events=jnp.zeros(n_cells, jnp.int32),
            acq_t0=jnp.full((n_cells, n_threads), -1, jnp.int32),
            lat_hist=jnp.zeros((n_cells, N_LAT_BUCKETS), jnp.int32),
        )
        vstep = jax.vmap(_step)

        def cond(s: SimState):
            t_th = s.next_time.min(1)
            t_cm = jnp.where(s.pend_addr >= 0, s.pend_time, INF).min(1)
            return jnp.any((s.events < c.max_events)
                           & (jnp.minimum(t_th, t_cm) < c.horizon))

        final = jax.lax.while_loop(cond, functools.partial(vstep, c), s0)
        return {
            "acquisitions": final.acq,
            "waited_acquisitions": final.waited_acq,
            "handover_sum": final.hand_sum,
            "handover_count": final.hand_cnt,
            "events": final.events,
            "sleeping": (final.spin_addr >= 0).sum(1),
            "grant_value": final.mem,
            "lat_hist": final.lat_hist,
        }

    return run


def _make_run_map(n_threads: int, mem_words: int, n_locks: int):
    """Batched driver variant: ``lax.map`` of the single-cell driver.

    Same one-compile-per-sweep property and identical results as the vmapped
    driver, but cells execute sequentially inside the compiled program.  On
    CPU this wins: a lane-parallel sweep costs ``max(events) × B`` lane-steps
    (idle lanes still pay the switch) while the sequential map costs
    ``sum(events)`` — and scalar XLA scatters see no SIMD benefit anyway.
    """
    run = _make_run(n_threads, mem_words, n_locks)

    def run_map(*args):
        return jax.lax.map(lambda cell: run(*cell), args)

    return run_map


def _make_run_sched(n_threads: int, mem_words: int, n_locks: int,
                    n_lanes: int, chunk: int):
    """Chunked work-stealing lane scheduler over the batched step.

    ``n_lanes`` lanes run a ``vmap`` of the step in fixed-size ``chunk``-step
    bursts inside an outer ``lax.while_loop``.  After each burst, lanes whose
    cell terminated (same condition the single-cell driver stops on) scatter
    their stats into per-cell output slots and are refilled from the queue of
    not-yet-started cells — the queued cell's init state is gathered into the
    free lane.  Wall-clock therefore tracks ``sum(events) / n_lanes`` instead
    of vmap's ``max(events)``, and every cell still executes its private
    event sequence via the self-guarding step, so per-cell results are
    bit-identical to ``mode="map"`` — only lane placement changes.

    A lane whose queue ran dry parks with ``lane_cell = -1`` and a zero
    horizon, making its steps free no-events until the loop ends.
    """

    def run(program, init_pc, init_regs, init_mem, n_active, seed,
            horizon, max_events, costs, wa_base, wa_mask, wa_size, *faults):
        n_cells = program.shape[0]
        lanes = min(n_lanes, n_cells)

        def cell_init(i):
            return _initial_state(n_threads, mem_words, n_locks,
                                  init_pc[i], init_regs[i], init_mem[i],
                                  n_active[i], seed[i])

        def lane_consts(lane_cell):
            lc = jnp.clip(lane_cell, 0, n_cells - 1)
            occupied = lane_cell >= 0
            return SimConsts(
                program=program[lc], costs=costs[lc], wa_base=wa_base[lc],
                wa_mask=wa_mask[lc], wa_size=wa_size[lc],
                horizon=jnp.where(occupied, horizon[lc], 0),
                max_events=max_events[lc],
                **{k: v[lc] for k, v in _fault_fields(faults).items()})

        vstep = jax.vmap(_step)

        def cond(carry):
            lane_cell, next_cell, _, _ = carry
            return (next_cell < n_cells) | jnp.any(lane_cell >= 0)

        def body(carry):
            lane_cell, next_cell, s, outs = carry
            c = lane_consts(lane_cell)
            s = jax.lax.fori_loop(0, chunk, lambda _, st: vstep(c, st), s)
            # terminated lanes: exact negation of the step's ``live`` guard,
            # so a detected lane is at the precise state the single-cell
            # driver would have stopped in
            t_th = s.next_time.min(1)
            t_cm = jnp.where(s.pend_addr >= 0, s.pend_time, INF).min(1)
            fin = (lane_cell >= 0) & (
                (s.events >= c.max_events)
                | (jnp.minimum(t_th, t_cm) >= c.horizon))
            # scatter finished stats to their cell slot (index B = dropped)
            idx = jnp.where(fin, lane_cell, n_cells)
            outs = {
                "acquisitions":
                    outs["acquisitions"].at[idx].set(s.acq, mode="drop"),
                "waited_acquisitions":
                    outs["waited_acquisitions"].at[idx].set(s.waited_acq,
                                                            mode="drop"),
                "handover_sum":
                    outs["handover_sum"].at[idx].set(s.hand_sum, mode="drop"),
                "handover_count":
                    outs["handover_count"].at[idx].set(s.hand_cnt,
                                                       mode="drop"),
                "events": outs["events"].at[idx].set(s.events, mode="drop"),
                "sleeping":
                    outs["sleeping"].at[idx].set((s.spin_addr >= 0).sum(1),
                                                 mode="drop"),
                "grant_value":
                    outs["grant_value"].at[idx].set(s.mem, mode="drop"),
                "lat_hist":
                    outs["lat_hist"].at[idx].set(s.lat_hist, mode="drop"),
            }
            # work stealing: the i-th finished lane (in lane order) claims
            # queue slot next_cell + i; lanes past the queue end park
            rank = jnp.cumsum(fin.astype(jnp.int32)) - fin.astype(jnp.int32)
            cand = next_cell + rank
            gets = fin & (cand < n_cells)
            lane_cell = jnp.where(fin, jnp.where(gets, cand, -1), lane_cell)
            next_cell = jnp.minimum(next_cell + fin.sum(), n_cells)
            fresh = jax.vmap(cell_init)(jnp.clip(lane_cell, 0, n_cells - 1))
            s = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    gets.reshape((lanes,) + (1,) * (old.ndim - 1)), new, old),
                fresh, s)
            return lane_cell, next_cell, s, outs

        lane_cell0 = jnp.arange(lanes, dtype=jnp.int32)
        outs0 = {
            "acquisitions": jnp.zeros((n_cells, n_threads), jnp.int32),
            "waited_acquisitions": jnp.zeros((n_cells, n_threads), jnp.int32),
            "handover_sum": jnp.zeros(n_cells, jnp.int32),
            "handover_count": jnp.zeros(n_cells, jnp.int32),
            "events": jnp.zeros(n_cells, jnp.int32),
            "sleeping": jnp.zeros(n_cells, jnp.int32),
            "grant_value": jnp.zeros((n_cells, mem_words), jnp.int32),
            "lat_hist": jnp.zeros((n_cells, N_LAT_BUCKETS), jnp.int32),
        }
        carry = (lane_cell0, jnp.int32(lanes),
                 jax.vmap(cell_init)(lane_cell0), outs0)
        return jax.lax.while_loop(cond, body, carry)[3]

    return run


@functools.lru_cache(maxsize=256)
def _build_engine(n_threads: int, mem_words: int, n_locks: int, prog_len: int,
                  batched: str | None = None, n_lanes: int = 0,
                  chunk: int = 0, interpret: bool = False,
                  n_faults: int = 0):
    """Compile an engine for a given shape set (everything else is an input).

    The cache key is shapes only; ``prog_len`` rides along for cache identity
    even though jit would also specialize on it.  ``batched`` selects the
    sweep driver ("vmap" = lane-parallel, "map" = sequential cells, "sched" =
    work-stealing lanes keyed additionally on the ``n_lanes``/``chunk``
    geometry, "pallas" = the fused-kernel fast path keyed on ``chunk`` and
    the ``interpret`` flag); either way a sweep is one compile and one
    dispatch, not one per cell.  ``n_faults`` is the fault-schedule capacity:
    0 builds the fault-free step (no fault code traced at all); > 0 drivers
    take four trailing ``(B, n_faults)`` schedule arrays.
    """
    if batched == "sched":
        assert not interpret, "interpret only applies to mode='pallas'"
        return jax.jit(_make_run_sched(n_threads, mem_words, n_locks,
                                       n_lanes, chunk))
    if batched == "pallas":
        from .engine_pallas import make_run_pallas
        assert n_lanes == 0, (batched, n_lanes)
        return jax.jit(make_run_pallas(n_threads, mem_words, n_locks,
                                       prog_len, chunk, interpret,
                                       n_faults=n_faults))
    assert n_lanes == 0 and chunk == 0 and not interpret, \
        (batched, n_lanes, chunk, interpret)
    if batched == "vmap":
        return jax.jit(_make_run_batched(n_threads, mem_words, n_locks))
    if batched == "map":
        return jax.jit(_make_run_map(n_threads, mem_words, n_locks))
    assert batched is None, batched
    return jax.jit(_make_run(n_threads, mem_words, n_locks))


def engine_cache_info():
    """Compile-cache statistics (for tests asserting compile counts)."""
    return _build_engine.cache_info()


def _fault_arrays(faults) -> tuple:
    """Normalize a faults argument to a tuple of four (n_faults,) arrays."""
    if faults is None:
        return ()
    if isinstance(faults, FaultSchedule):
        faults = faults.padded(max(len(faults), 1))
    fk, fe, ft, fa = (np.asarray(a, np.int32) for a in faults)
    assert fk.shape == fe.shape == ft.shape == fa.shape and fk.ndim == 1, \
        (fk.shape, fe.shape, ft.shape, fa.shape)
    return (fk, fe, ft, fa)


def run_sim(program: np.ndarray, *, n_threads: int, mem_words: int,
            n_locks: int, init_pc: np.ndarray, init_regs: np.ndarray,
            wa_base: int, wa_size: int, horizon: int = 2_000_000,
            max_events: int = 2_000_000, seed: int = 1,
            costs: Costs = DEFAULT_COSTS, init_mem: np.ndarray | None = None,
            n_active: int | None = None, faults=None) -> dict:
    """Run a single lockVM program; returns python-side stats.

    Thin single-cell wrapper kept for backward compatibility; sweeps should
    go through :func:`run_sweep` (one compile, one dispatch for all cells).
    ``faults`` is an optional :class:`repro.sim.faults.FaultSchedule` (or a
    4-tuple of ``(n_faults,)`` int32 arrays).
    """
    assert wa_size & (wa_size - 1) == 0
    prog_len = PROG_LEN
    program = pad_program(program, prog_len)
    if init_mem is None:
        init_mem = np.zeros(mem_words, np.int32)
    if n_active is None:
        n_active = n_threads
    fault_args = _fault_arrays(faults)
    engine = _build_engine(n_threads, mem_words, n_locks, prog_len,
                           n_faults=fault_args[0].shape[0] if fault_args
                           else 0)
    out = engine(jnp.asarray(program), jnp.asarray(init_pc),
                 jnp.asarray(init_regs), jnp.asarray(init_mem),
                 jnp.int32(n_active), jnp.uint32(seed),
                 jnp.int32(horizon), jnp.int32(max_events),
                 jnp.asarray(costs.to_array()),
                 jnp.int32(wa_base), jnp.int32(wa_size - 1),
                 jnp.int32(wa_size), *(jnp.asarray(a) for a in fault_args))
    mem = np.asarray(out.pop("grant_value"))
    res = {k: np.asarray(v) for k, v in out.items()}
    res["mem"] = mem
    res["horizon"] = horizon
    res["throughput"] = float(res["acquisitions"].sum()) / horizon
    hc = int(res["handover_count"])
    res["avg_handover"] = float(res["handover_sum"]) / hc if hc else float("nan")
    return res


@functools.lru_cache(maxsize=1)
def _jit_step():
    """One jitted copy of the single-event transition (shape-specialized by
    jax on first use per shape set) — the debug-stepping entry point."""
    return jax.jit(_step)


def debug_states(program: np.ndarray, *, n_threads: int, mem_words: int,
                 n_locks: int, init_pc: np.ndarray, init_regs: np.ndarray,
                 wa_base: int, wa_size: int, horizon: int,
                 max_events: int = 2_000_000, seed: int = 1,
                 costs: Costs | np.ndarray = DEFAULT_COSTS,
                 init_mem: np.ndarray | None = None,
                 n_active: int | None = None, faults=None):
    """Single-cell debug entry: yield the full :class:`SimState` (as numpy)
    after EVERY event, in the engine's own event order.

    This is the observability hook for the ``sim.check`` subsystem: when the
    differential fuzzer finds an oracle/engine stat divergence, stepping both
    sides event by event against :data:`EVENT_ORDER_CONTRACT` pinpoints the
    first diverging event instead of leaving a whole-run diff.  The loop
    condition is exactly the compiled driver's (`events < max_events` and the
    earliest event time below ``horizon``), so the final yielded state equals
    :func:`run_sim`'s final state bit for bit.

    Costs one XLA compile of the single step per shape set (cached), then one
    dispatch per event — use small horizons.
    """
    assert wa_size & (wa_size - 1) == 0
    if isinstance(costs, Costs):
        costs = costs.to_array()
    if init_mem is None:
        init_mem = np.zeros(mem_words, np.int32)
    if n_active is None:
        n_active = n_threads
    c = SimConsts(program=jnp.asarray(pad_program(program)),
                  costs=jnp.asarray(costs, jnp.int32),
                  wa_base=jnp.int32(wa_base), wa_mask=jnp.int32(wa_size - 1),
                  wa_size=jnp.int32(wa_size), horizon=jnp.int32(horizon),
                  max_events=jnp.int32(max_events),
                  **{k: jnp.asarray(v)
                     for k, v in _fault_fields(_fault_arrays(faults)).items()})
    s = _initial_state(n_threads, mem_words, n_locks,
                       jnp.asarray(init_pc), jnp.asarray(init_regs),
                       jnp.asarray(init_mem), jnp.int32(n_active),
                       jnp.uint32(seed))
    step = _jit_step()
    while True:
        t_th, t_cm = _event_times(s)
        if not (int(s.events) < max_events
                and min(int(t_th), int(t_cm)) < horizon):
            return
        s = step(c, s)
        yield SimState(*(np.asarray(x) for x in s))


def _broadcast_cells(x, n_cells: int, dtype) -> np.ndarray:
    arr = np.asarray(x, dtype)
    if arr.ndim == 0:
        arr = np.full(n_cells, arr, dtype)
    assert arr.shape == (n_cells,), (arr.shape, n_cells)
    return arr


# Scheduler defaults, tuned on CPU: few lanes (the per-step cost of the
# scalar scatter/gather step scales with lane count there) and bursts long
# enough to amortize the refill check's gather/select over the lane state.
DEFAULT_LANES = 4
DEFAULT_CHUNK = 512

# mode="auto" thresholds: a sweep is "skewed" when its heaviest cell carries
# at least twice the mean estimated work and there are enough cells for the
# work-stealing scheduler to amortize its refill machinery.
AUTO_SKEW_RATIO = 2.0
AUTO_SKEW_MIN_CELLS = 8


def choose_mode(backend: str, *, n_cells: int, n_threads: int,
                horizon, n_active=None) -> str:
    """Pick a sweep driver from the backend kind and the sweep shape.

    The decision surface (all drivers are bit-identical, so this is purely
    a performance policy):

    * a *skewed* sweep (one cell's estimated work ≥ ``AUTO_SKEW_RATIO`` ×
      the mean, with at least ``AUTO_SKEW_MIN_CELLS`` cells) goes to the
      work-stealing ``"sched"`` driver, which keeps lanes busy across the
      skew, on every backend;
    * otherwise **tpu** runs the cells lane-parallel (``"vmap"``), and the
      host backends run them one after another (``"map"``): a scalar step
      sees no SIMD benefit there, so ``"map"`` pays exactly ``sum(events)``.

    ``"pallas"`` is never picked: Mosaic refuses its kernel
    (:data:`repro.sim.engine_pallas.TPU_REFUSAL`).  Work per cell is
    estimated as ``horizon × n_active`` — the event count is horizon-bound
    for live cells and padded threads never run.
    """
    horizon = np.broadcast_to(np.asarray(horizon, np.int64), (n_cells,))
    if n_active is None:
        n_active = n_threads
    n_active = np.broadcast_to(np.asarray(n_active, np.int64), (n_cells,))
    est = horizon * n_active
    skewed = (n_cells >= AUTO_SKEW_MIN_CELLS
              and est.max() * n_cells >= AUTO_SKEW_RATIO * est.sum())
    if skewed:
        return "sched"
    return "vmap" if backend == "tpu" else "map"


def run_sweep(programs: np.ndarray, *, mem_words: int, n_locks: int,
              init_pc: np.ndarray, init_regs: np.ndarray,
              n_active, seeds, wa_base, wa_size,
              horizon, max_events=2_000_000, costs=None,
              init_mem: np.ndarray | None = None,
              mode: str = "auto", lanes: int | None = None,
              chunk: int | None = None, interpret: bool | None = None,
              live_mem_words=None, faults=None) -> dict:
    """Run a batch of independent simulations as ONE compiled, vmapped call.

    Every per-cell argument carries a leading batch axis of size B; scalars
    broadcast.  All cells must share the padded shapes ``(n_threads,
    mem_words, n_locks, prog_len)`` — pad programs/threads/memory to the
    sweep-wide maximum (see ``repro.sim.programs`` helpers) and mark padded
    threads inactive via ``n_active``.

    Args:
      programs:  (B, prog_len, 5) int32.
      mem_words: padded memory size shared by every cell.
      n_locks:   padded lock-table size shared by every cell.
      init_pc:   (B, n_threads) int32.
      init_regs: (B, n_threads, N_REGS) int32.
      n_active:  (B,) or scalar — threads beyond this index never run.
      seeds:     (B,) or scalar uint32.
      wa_base/wa_size: (B,) or scalar waiting-array geometry (wa_size must be
        a power of two; the engine derives the mask).
      horizon/max_events: (B,) or scalar int32.
      costs:     Costs, (9,) array, or (B, 9) array; default DEFAULT_COSTS.
      init_mem:  (B, mem_words) int32 or None for all-zeros.
      mode:      "vmap" runs all cells lane-parallel (best on accelerators
        with uniform cells), "map" runs them sequentially inside one compiled
        program, "sched" runs a work-stealing lane scheduler (pays
        ~sum(events) like "map" but keeps ``lanes`` cells in flight — the
        right choice for skewed sweeps), "pallas" fuses each cell's whole
        event loop into one Pallas-kernel grid step (interpret mode only:
        natively it raises ``NotImplementedError``, as Mosaic refuses the
        kernel), "auto" picks by backend kind + sweep shape
        (:func:`choose_mode`).  Results are bit-identical across all modes.
      lanes/chunk: driver geometry — ``lanes`` ("sched" only) is the number
        of parallel work-stealing lanes (clamped to B); ``chunk`` ("sched"
        and "pallas") is the steps per burst between termination checks.
      interpret: "pallas" only — force the Pallas interpreter on/off; None
        autodetects (interpret unless the backend is a TPU).
      live_mem_words: optional (B,) per-cell *unpadded* memory sizes, used
        only for the ``pad_stats`` waste report (defaults to ``mem_words``,
        i.e. no padding assumed).
      faults: optional per-cell fault schedules — a 4-tuple of
        ``(B, n_faults)`` int32 arrays ``(kind, evt, tid, arg)`` as produced
        by :func:`repro.sim.faults.stack_schedules`.  None (the default)
        builds the fault-free step: zero-fault sweeps are bit-identical to
        the pre-fault-subsystem engine.

    Returns a dict of stacked numpy arrays: per-thread stats have shape
    (B, n_threads), scalars (B,), and ``grant_value`` (B, mem_words) holds
    each cell's final memory.  Two bookkeeping keys ride along: ``mode``
    (the resolved driver, useful under "auto") and ``pad_stats`` — the
    sweep's padding-waste report (``sum_events``/``max_events`` plus the
    live thread/program/memory fractions of the padded batch).
    """
    programs = np.asarray(programs, np.int32)
    assert programs.ndim == 3 and programs.shape[2] == 5, programs.shape
    n_cells, prog_len = programs.shape[0], programs.shape[1]
    init_pc = np.asarray(init_pc, np.int32)
    init_regs = np.asarray(init_regs, np.int32)
    n_threads = init_pc.shape[1]
    assert init_pc.shape == (n_cells, n_threads)
    assert init_regs.shape[:2] == (n_cells, n_threads)

    if mode == "auto":
        backend = jax.default_backend()
        mode = choose_mode(backend, n_cells=n_cells, n_threads=n_threads,
                           horizon=horizon, n_active=n_active)
        log.info("run_sweep mode='auto' -> %r (backend=%s, B=%d, "
                 "n_threads=%d, mem_words=%d)", mode, backend, n_cells,
                 n_threads, mem_words)
    assert mode in ("vmap", "map", "sched", "pallas"), mode
    if mode == "sched":
        lanes = DEFAULT_LANES if lanes is None else lanes
        chunk = DEFAULT_CHUNK if chunk is None else chunk
        assert lanes >= 1 and chunk >= 1, (lanes, chunk)
    elif mode == "pallas":
        from ..kernels import resolve_interpret
        from .engine_pallas import DEFAULT_PALLAS_CHUNK, TPU_REFUSAL
        assert lanes is None, "lanes only applies to mode='sched'"
        lanes = 0
        chunk = DEFAULT_PALLAS_CHUNK if chunk is None else chunk
        assert chunk >= 1, chunk
        interpret = resolve_interpret(interpret)
        if not interpret:
            raise NotImplementedError(TPU_REFUSAL)
    else:
        assert lanes is None and chunk is None, \
            f"lanes/chunk only apply to mode='sched'/'pallas', " \
            f"got mode={mode!r}"
        lanes = chunk = 0
    if mode != "pallas":
        assert interpret is None, "interpret only applies to mode='pallas'"
        interpret = False

    wa_size_arr = _broadcast_cells(wa_size, n_cells, np.int32)
    assert (wa_size_arr & (wa_size_arr - 1) == 0).all(), "wa_size must be pow2"
    if costs is None:
        costs = DEFAULT_COSTS
    if isinstance(costs, Costs):
        costs = costs.to_array()
    costs = np.asarray(costs, np.int32)
    if costs.ndim == 1:
        costs = np.broadcast_to(costs, (n_cells, 9))
    if init_mem is None:
        init_mem = np.zeros((n_cells, mem_words), np.int32)
    init_mem = np.asarray(init_mem, np.int32)
    assert init_mem.shape == (n_cells, mem_words), init_mem.shape

    if faults is not None:
        fault_args = tuple(np.asarray(a, np.int32) for a in faults)
        assert len(fault_args) == 4, len(fault_args)
        n_faults = fault_args[0].shape[1]
        for a in fault_args:
            assert a.shape == (n_cells, n_faults), (a.shape, n_cells, n_faults)
    else:
        fault_args, n_faults = (), 0

    n_active_arr = _broadcast_cells(n_active, n_cells, np.int32)
    engine = _build_engine(n_threads, mem_words, n_locks, prog_len,
                           batched=mode, n_lanes=lanes, chunk=chunk,
                           interpret=interpret, n_faults=n_faults)
    out = engine(jnp.asarray(programs), jnp.asarray(init_pc),
                 jnp.asarray(init_regs), jnp.asarray(init_mem),
                 jnp.asarray(n_active_arr),
                 jnp.asarray(_broadcast_cells(seeds, n_cells, np.uint32)),
                 jnp.asarray(_broadcast_cells(horizon, n_cells, np.int32)),
                 jnp.asarray(_broadcast_cells(max_events, n_cells, np.int32)),
                 jnp.asarray(costs),
                 jnp.asarray(_broadcast_cells(wa_base, n_cells, np.int32)),
                 jnp.asarray(wa_size_arr - 1),
                 jnp.asarray(wa_size_arr),
                 *(jnp.asarray(a) for a in fault_args))
    res = {k: np.asarray(v) for k, v in out.items()}
    res["mode"] = mode
    res["pad_stats"] = _pad_stats(
        programs, n_active_arr, n_threads, res["events"],
        _broadcast_cells(mem_words if live_mem_words is None
                         else live_mem_words, n_cells, np.int64), mem_words)
    return res


def _pad_stats(programs: np.ndarray, n_active: np.ndarray, n_threads: int,
               events: np.ndarray, live_mem: np.ndarray,
               mem_words: int) -> dict:
    """Padding-waste report for one sweep dispatch.

    Batched cells are padded to shared shapes, and the padding is pure
    overhead the drivers carry: inactive threads still occupy rows in every
    per-thread gather/scatter, padded program rows occupy the instruction
    table, padded memory words occupy hot state (and sharer-bitset lines).
    ``bench_engine`` and fuzz runs report these fractions so packer
    regressions are visible instead of silently eaten as wall-clock.
    """
    from .isa import HALT
    n_cells, prog_len = programs.shape[0], programs.shape[1]
    # live program rows: everything up to the last row that is not the
    # canonical (HALT, 0, 0, 0, 0) pad row pad_program appends
    pad_row = (programs[:, :, 0] == HALT) & (programs[:, :, 1:] == 0).all(-1)
    live = ~pad_row
    live_rows = np.where(live.any(axis=1),
                         prog_len - np.argmax(live[:, ::-1], axis=1), 0)
    return {
        "sum_events": int(events.sum()),
        "max_events": int(events.max()) if n_cells else 0,
        "live_thread_frac": float(n_active.sum() / (n_cells * n_threads)),
        "live_prog_frac": float(live_rows.sum() / (n_cells * prog_len)),
        "live_mem_frac": float(live_mem.sum() / (n_cells * mem_words)),
    }
