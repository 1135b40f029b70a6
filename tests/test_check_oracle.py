"""sim.check oracle validation: the pure-NumPy reference interpreter must
match the compiled engine bit for bit on every lock program, its event
trace must witness ticket FIFO, and the engine's debug-stepping entry must
agree with both."""

import numpy as np

from repro.sim import SIM_LOCKS, Layout, build_mutexbench, \
    build_occupancy_probe, init_state
from repro.sim.check import Trace, run_oracle
from repro.sim.engine import (EVENT_ORDER_CONTRACT, OUT_KEYS, debug_states,
                              run_sim)
from repro.sim.programs import INIT_MEM_GEN, pad_program

# run_sim returns the final memory as "mem", compared on its own
STAT_KEYS = tuple(k for k in OUT_KEYS if k != "grant_value")
H = 12_000


def _cell(lock, *, builder=build_mutexbench, horizon=H, seed=2, **layout_kw):
    layout_kw.setdefault("n_threads", 4)
    layout_kw.setdefault("n_locks", 1)
    layout_kw.setdefault("wa_size", 64)
    layout = Layout(**layout_kw)
    prog = builder(lock, layout)
    pc, regs = init_state(layout)
    gen_mem = INIT_MEM_GEN.get(lock)
    kw = dict(n_threads=layout.n_threads, mem_words=layout.mem_words,
              n_locks=layout.n_locks, init_pc=pc, init_regs=regs,
              wa_base=layout.wa_base, wa_size=layout.wa_size,
              horizon=horizon, max_events=100_000, seed=seed,
              init_mem=gen_mem(layout) if gen_mem else None)
    return prog, kw


def _assert_match(prog, kw, trace=None):
    eng = run_sim(prog, **kw)
    orc = run_oracle(pad_program(prog), trace=trace, **kw)
    for k in STAT_KEYS:
        assert np.array_equal(np.asarray(eng[k]), np.asarray(orc[k])), k
    assert np.array_equal(eng["mem"], orc["grant_value"])
    return eng, orc


def test_oracle_matches_engine_every_lock():
    """All 14 SIM_LOCKS mutexbench programs: every stat and the final
    memory must be bit-identical between oracle and engine."""
    for lock in SIM_LOCKS:
        prog, kw = _cell(lock)
        _assert_match(prog, kw)


def test_oracle_matches_engine_probe_multilock():
    """Occupancy-probe programs over two locks (random per-iteration lock
    choice exercises PRNG + MULI paths) must match too."""
    for lock in ("ticket", "twa", "twa-sem", "clh"):
        prog, kw = _cell(lock, builder=build_occupancy_probe, n_locks=2,
                         n_threads=5, sem_permits=2)
        _assert_match(prog, kw)


def test_oracle_trace_witnesses_ticket_fifo():
    """The oracle's ACQ trace must show strictly increasing tickets for a
    ticket lock — the observable the compiled engine cannot provide."""
    prog, kw = _cell("ticket")
    trace = Trace()
    eng, orc = _assert_match(prog, kw, trace=trace)
    assert trace.exit_reason == "horizon"
    assert len(trace.acquires) == int(np.asarray(orc["acquisitions"]).sum())
    tickets = [tk for (_e, _n, _t, _l, _w, tk) in trace.acquires]
    assert tickets == sorted(tickets)
    assert len(set(tickets)) == len(tickets)


def test_oracle_collision_tally_matches_engine():
    """count_collisions instrumentation (node-sector stores) is covered by
    the differential too."""
    prog, kw = _cell("twa", wa_size=8, n_threads=6,
                     count_collisions=True, long_term_threshold=1)
    _assert_match(prog, kw)


def test_oracle_mirrors_engine_on_out_of_range_operand_fields():
    """Const-role instruction fields live in the same slots as register
    indices and are read unconditionally by both sides; XLA wraps one
    negative cycle then clamps gathers / drops scatters.  The oracle must
    mirror that exactly rather than crash or mis-read (e.g. STOREI of
    constant 100, FADD addend -20, a write to 'register 20')."""
    from repro.sim import isa
    prog = np.asarray([
        [isa.MOVI, 13, 0, 0, 9],
        [isa.STOREI, isa.R_LOCK, 100, 0, 3],  # const 100 in the b field
        [isa.MOV, isa.R_U, -3, 0, 0],         # read reg -3 -> wraps to 13
        [isa.MOV, isa.R_V, -20, 0, 0],        # read reg -20 -> clamps to 0
        [isa.MOV, isa.R_K, 99, 0, 0],         # read reg 99 -> clamps to 15
        [isa.MOVI, 20, 0, 0, 7],              # write reg 20 -> dropped
        [isa.MOVI, -3, 0, 0, 4],              # write reg -3 -> wraps to 13
        [isa.FADD, isa.R_U, isa.R_LOCK, -20, 4],
        [isa.STORE, isa.R_LOCK, isa.R_T1, 0, 5],
        [isa.HALT, 0, 0, 0, 0]], np.int32)
    pc = np.zeros(2, np.int32)
    regs = np.zeros((2, isa.N_REGS), np.int32)
    regs[:, 15] = 77
    kw = dict(n_threads=2, mem_words=64, n_locks=1, init_pc=pc,
              init_regs=regs, wa_base=32, wa_size=8, horizon=5000,
              max_events=10_000, seed=5)
    eng = run_sim(prog, **kw)
    orc = run_oracle(pad_program(prog), **kw)
    for k in STAT_KEYS:
        assert np.array_equal(np.asarray(eng[k]), np.asarray(orc[k])), k
    assert np.array_equal(eng["mem"], orc["grant_value"])


def test_debug_states_replays_the_engine_event_by_event():
    """The single-cell debug entry must stop in exactly run_sim's final
    state: same event count, same stats, same memory."""
    prog, kw = _cell("twa", horizon=1_500)
    eng = run_sim(prog, **kw)
    final = None
    n_events = 0
    for final in debug_states(prog, **kw):
        n_events += 1
    assert final is not None
    assert n_events == int(eng["events"]) == int(final.events)
    assert np.array_equal(final.acq, eng["acquisitions"])
    assert np.array_equal(final.mem, eng["mem"])
    assert int((final.spin_addr >= 0).sum()) == int(eng["sleeping"])


def test_event_order_contract_is_shared():
    """The oracle re-exports the engine's contract object — a divergence in
    event ordering must be a deliberate two-sided edit, not drift."""
    from repro.sim.check import oracle
    assert oracle.EVENT_ORDER_CONTRACT is EVENT_ORDER_CONTRACT
    assert "commit" in EVENT_ORDER_CONTRACT
