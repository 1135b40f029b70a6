"""Fault injection end-to-end: schedule plumbing, zero-fault bit-identity,
fault-enabled differential fuzz across all three sweep drivers, the
``dropped_fault`` checker self-test, the robustness invariant classes
(``lost_grant`` / ``recovery`` / ``abandoned``), the timed/abortable
``twa-timo`` lock's in-VM abandonment books, and the program-splicing
mutator."""

import numpy as np
import pytest

from repro.sim.check import (case_problems, failure_classes, fuzz,
                             generate_batch, load_scenario, save_scenario,
                             scenario_faults, splice_programs,
                             with_fault_schedule)
from repro.sim.check.generate import _harness_body_span, mutate_scenario
from repro.sim.faults import (F_ABORT, F_NONE, F_PREEMPT, F_SPURIOUS,
                              FaultSchedule, draw_schedule, stack_schedules)

BATCH_SEED = 321
N_CASES = 22  # every SIM_LOCKS entry composed once + random programs


@pytest.fixture(scope="module")
def fault_batch():
    return generate_batch(N_CASES, BATCH_SEED, fault_fraction=1.0)


# ---------------------------------------------------------------------------
# FaultSchedule plumbing
# ---------------------------------------------------------------------------

def test_draw_schedule_is_deterministic_and_valid():
    rng = np.random.default_rng(7)
    s = draw_schedule(rng, n_active=4, max_events=1000,
                      n_preempt=3, n_spurious=2, n_abort=1)
    again = draw_schedule(np.random.default_rng(7), n_active=4,
                          max_events=1000, n_preempt=3, n_spurious=2,
                          n_abort=1)
    assert np.array_equal(s.evt, again.evt)
    assert np.array_equal(s.kind, again.kind)
    assert len(s) == 6
    assert len(set(s.evt.tolist())) == 6        # unique event indices
    assert (np.diff(s.evt) > 0).all()           # sorted
    assert s.counts() == {"preempt": 3, "spurious": 2, "abort": 1}
    assert ((s.arg > 0) == (s.kind == F_PREEMPT)).all()
    s.validate(n_threads=4, max_events=1000)


def test_fault_schedule_roundtrips_through_json_rows():
    rng = np.random.default_rng(8)
    s = draw_schedule(rng, n_active=3, max_events=500, n_preempt=2,
                      n_abort=1)
    rows = s.to_lists()
    back = FaultSchedule.from_lists(rows)
    for f in ("kind", "evt", "tid", "arg"):
        assert np.array_equal(getattr(s, f), getattr(back, f))
    assert FaultSchedule.from_lists([]) .n == 0


def test_stack_schedules_pads_with_f_none():
    rng = np.random.default_rng(9)
    a = draw_schedule(rng, n_active=2, max_events=100, n_preempt=1)
    b = draw_schedule(rng, n_active=2, max_events=100, n_preempt=3)
    kind, evt, tid, arg = stack_schedules([a, FaultSchedule.empty(), b])
    assert kind.shape == evt.shape == tid.shape == arg.shape == (3, 3)
    assert (kind[1] == F_NONE).all()            # empty row is all padding
    assert (kind[0, 1:] == F_NONE).all()        # short row padded out
    assert kind.dtype == np.int32


# ---------------------------------------------------------------------------
# Generator decoration + zero-fault bit-identity
# ---------------------------------------------------------------------------

def test_fault_fraction_zero_reproduces_historical_batches():
    plain = generate_batch(12, BATCH_SEED)
    zero = generate_batch(12, BATCH_SEED, fault_fraction=0.0)
    for a, b in zip(plain, zero):
        assert np.array_equal(a.program, b.program)
        assert a.meta == b.meta
        assert scenario_faults(a) is None


def test_fault_fraction_one_decorates_every_case(fault_batch):
    for s in fault_batch:
        sched = scenario_faults(s)
        assert sched is not None and len(sched) >= 1
        sched.validate(n_threads=s.n_active, max_events=s.max_events)


def test_fault_schedule_survives_the_corpus_roundtrip(tmp_path, fault_batch):
    path = tmp_path / "faulty.npz"
    save_scenario(path, fault_batch[0])
    loaded = load_scenario(path)
    a, b = scenario_faults(fault_batch[0]), scenario_faults(loaded)
    assert np.array_equal(a.kind, b.kind) and np.array_equal(a.evt, b.evt)


def test_padded_f_none_rows_are_bitwise_noops():
    """The engine must treat all-F_NONE fault rows exactly like
    ``faults=None`` — pinned through the SweepSpec fault axes: the
    zero-preemption cells of a fault sweep replay bit-identically to a
    dedicated fault-free sweep."""
    from dataclasses import replace

    from repro.sim.workloads import SweepSpec, run_sweep
    base = SweepSpec(locks=("ticket", "twa-timo"), threads=4, seeds=1,
                     horizon=20_000, max_events=40_000)
    clean = run_sweep(base)
    mixed = run_sweep(replace(base, preempt_faults=(0, 2),
                              fault_evt_span=1500))
    zero = [r for r in mixed if r["preempt_faults"] == 0]
    assert len(zero) == len(clean)
    degraded = False
    for a, b in zip(clean, zero):
        assert np.array_equal(a["mem"], b["mem"]), a["lock"]
        assert a["throughput"] == b["throughput"]
    for r in mixed:
        if r["preempt_faults"]:
            assert len(r["fault_schedule"]) == 2
            degraded = True
    assert degraded


def test_sweep_fault_schedules_are_coordinate_keyed():
    from repro.sim.workloads import SweepSpec
    spec = SweepSpec(locks=("ticket", "twa"), threads=4, seeds=(1, 2),
                     preempt_faults=2, fault_evt_span=1000)
    cells = spec.cells()
    scheds = [spec.fault_schedule_for(c) for c in cells]
    by_coord = {}
    for c, s in zip(cells, scheds):
        key = (c.seed, c.n_threads)
        if key in by_coord:  # same coordinates -> same schedule, any lock
            assert np.array_equal(by_coord[key].evt, s.evt)
        by_coord[key] = s
    # distinct seeds draw distinct schedules
    assert not np.array_equal(by_coord[(1, 4)].evt, by_coord[(2, 4)].evt)


# ---------------------------------------------------------------------------
# Differential fuzz under faults + checker self-tests
# ---------------------------------------------------------------------------

def test_fault_fuzz_is_clean_across_all_modes(fault_batch):
    """The acceptance sweep in miniature, faults on: oracle stats ==
    run_sweep stats bit-identically across map/vmap/sched with
    every case carrying a drawn fault schedule."""
    report = fuzz(fault_batch)
    assert report.ok, report.summary()


def test_dropped_fault_mutation_is_caught(fault_batch):
    """Checker self-test: an oracle that silently skips scheduled faults
    MUST diverge from the engine — through the sequential oracle AND the
    batch-oracle/C path.  On a fault-free batch the same mutation is a
    no-op and must NOT fire (it only drops faults, nothing else)."""
    report = fuzz(fault_batch, modes=("map",),
                  oracle_mutate=("dropped_fault",))
    assert not report.ok, "dropped_fault was not caught"
    report_b = fuzz(fault_batch, modes=("map",),
                    oracle_mutate=("dropped_fault",), batch_oracle=True)
    assert not report_b.ok, "dropped_fault not caught via batch oracle"
    clean = generate_batch(8, BATCH_SEED + 1)
    noop = fuzz(clean, modes=("map",), oracle_mutate=("dropped_fault",))
    assert noop.ok, noop.summary()


def test_lost_wake_is_caught_by_the_lost_grant_invariant():
    """The ``lost_grant`` class convicts a lost-wake bug with NO
    differential at all (``modes=()``): a thread left parked on a word
    whose final value satisfies its predicate is itself the witness."""
    batch = generate_batch(N_CASES, 123)
    hits = 0
    for s in batch:
        got = failure_classes(case_problems(
            s, modes=(), oracle_mutate=("lost_wake",)))
        hits += "lost_grant" in got
        clean = failure_classes(case_problems(s, modes=()))
        assert "lost_grant" not in clean, s.lock
    assert hits >= 5, hits


def test_deadlock_and_progress_gate_off_under_faults(fault_batch):
    from repro.sim.check import active_classes
    for s in fault_batch:
        classes = set(active_classes(s))
        assert "deadlock" not in classes
        assert "progress" not in classes
        assert "lost_grant" in classes
        if s.kind == "composed":
            sched = scenario_faults(s)
            has_abort = bool((sched.kind == F_ABORT).any())
            assert ("recovery" in classes) == (not has_abort)


def test_recovery_check_unit():
    from repro.sim.check.invariants import check_recovery
    from repro.sim.check.oracle import Trace
    rng = np.random.default_rng(5)
    s = next(x for x in generate_batch(8, BATCH_SEED, fault_fraction=1.0)
             if x.kind == "composed"
             and not (scenario_faults(x).kind == F_ABORT).any())
    stalled = Trace()
    stalled.exit_reason = "stalled"
    assert check_recovery(s, stalled)           # transient-only: flags
    halted = Trace()
    halted.exit_reason = "horizon"
    assert check_recovery(s, halted) == []
    # an abort schedule legitimately stalls strict-FIFO waiters: gated off
    aborted = s.replace(meta={**s.meta, "faults": draw_schedule(
        rng, n_active=s.n_active, max_events=s.max_events,
        n_abort=1).to_lists()})
    assert check_recovery(aborted, stalled) == []


# ---------------------------------------------------------------------------
# twa-timo: timed/abortable acquisition
# ---------------------------------------------------------------------------

def test_twa_timo_abandoned_tickets_are_skipped_exactly_once():
    """In-VM probe of the abandonment arbitration, run to completion: a
    bounded-iteration workload with patience 1 and a long CS forces
    timeouts; at halt every drawn ticket was either acquired or abandoned,
    every abandoned marker was consumed by a releaser exactly once
    (``skipped == abandoned``), and the grant caught up with the ticket
    counter (no wedge, no double-skip)."""
    from repro.sim import isa
    from repro.sim.check.oracle import Trace, run_oracle
    from repro.sim.programs import (ACQUIRE_GEN, RELEASE_GEN, Asm, Layout,
                                    TIMO_ABANDONED_OFF, TIMO_SKIPPED_OFF,
                                    WORK_SCALE, init_state)
    iters, n_threads = 4, 3
    layout = Layout(n_threads=n_threads, n_locks=1, timo_patience=1)
    asm = Asm()
    asm.emit(isa.MOVI, isa.R_NX, 0, 0, iters)
    asm.label("top")
    ACQUIRE_GEN["twa-timo"](asm, "a", layout)
    asm.emit(isa.WORKI, 0, 0, 0, 40 * WORK_SCALE)
    RELEASE_GEN["twa-timo"](asm, "r", layout)
    asm.emit(isa.ADDI, isa.R_NX, isa.R_NX, 0, -1)
    asm.emit(isa.BGTI, isa.R_NX, 0, 0, "top")
    asm.emit(isa.HALT, 0, 0, 0, 0)
    prog = asm.finish()
    pc, regs = init_state(layout)
    trace = Trace()
    out = run_oracle(prog, n_threads=n_threads,
                     mem_words=layout.mem_words, n_locks=1,
                     init_pc=pc, init_regs=regs, wa_base=layout.wa_base,
                     wa_size=layout.wa_size, horizon=2_000_000,
                     max_events=2_000_000, trace=trace)
    assert trace.exit_reason == "halted"
    acq = int(np.asarray(out["acquisitions"]).sum())
    assert acq == iters * n_threads         # every iteration acquired once
    mem = np.asarray(out["grant_value"])
    ticket = int(mem[isa.OFF_TICKET])
    grant = int(mem[isa.OFF_GRANT])
    abandoned = int(mem[TIMO_ABANDONED_OFF])
    skipped = int(mem[TIMO_SKIPPED_OFF])
    assert abandoned >= 1, "patience 1 under contention never timed out"
    assert skipped == abandoned             # each marker consumed once
    assert ticket == grant                  # books balance at halt
    assert ticket == acq + abandoned        # every draw resolved


def test_twa_timo_composed_scenarios_are_clean_and_abandon():
    """Composed twa-timo scenarios across random geometries: zero
    problems on the map differential + the full invariant catalog (incl.
    the ``abandoned`` books), with at least one geometry actually
    abandoning."""
    from repro.sim.check import gen_composed_scenario, run_oracle_case
    from repro.sim.programs import TIMO_ABANDONED_OFF
    rng = np.random.default_rng(11)
    abandoned_total = 0
    for _ in range(6):
        s = gen_composed_scenario(rng, "twa-timo", n_locks=1)
        assert case_problems(s, modes=("map",)) == []
        out, _ = run_oracle_case(s)
        mem = np.asarray(out["grant_value"])
        abandoned_total += int(mem[TIMO_ABANDONED_OFF]) - int(
            np.asarray(s.init_mem)[TIMO_ABANDONED_OFF])
    assert abandoned_total >= 1


def test_abandoned_books_convict_corrupted_counters():
    from repro.sim.check import gen_composed_scenario, run_oracle_case
    from repro.sim.check.invariants import check_abandoned
    from repro.sim.programs import TIMO_SKIPPED_OFF
    rng = np.random.default_rng(13)
    s = gen_composed_scenario(rng, "twa-timo", n_locks=1)
    out, _ = run_oracle_case(s)
    mem = np.asarray(out["grant_value"]).copy()
    assert check_abandoned(s, mem, out) == []
    bad = mem.copy()
    bad[TIMO_SKIPPED_OFF] += 1000           # phantom skips
    assert check_abandoned(s, bad, out)
    from repro.sim.isa import OFF_GRANT
    bad2 = mem.copy()
    bad2[OFF_GRANT] += 1000                 # grant running past the ticket
    assert check_abandoned(s, bad2, out)


# ---------------------------------------------------------------------------
# Mutation: fault redraw + program splicing
# ---------------------------------------------------------------------------

def test_mutate_redraws_fault_schedules(fault_batch):
    rng = np.random.default_rng(3)
    s = fault_batch[0]
    orig = scenario_faults(s)
    changed = False
    for _ in range(40):
        m = mutate_scenario(s, rng)
        sched = scenario_faults(m)
        assert sched is not None  # decoration is never silently dropped
        if not (len(sched) == len(orig)
                and np.array_equal(sched.evt, orig.evt)):
            changed = True
    assert changed


def test_splice_preserves_the_guaranteed_halt_harness():
    """Spliced programs must keep the MOVI-counter prologue and the
    decrement/branch/HALT epilogue intact, with every transplanted branch
    target remapped into the target's body."""
    from repro.sim.isa import OPCODES
    batch = [s for s in generate_batch(16, 77) if s.kind == "random"]
    assert len(batch) >= 2
    rng = np.random.default_rng(4)
    spliced_any = False
    for i in range(len(batch) - 1):
        out = splice_programs(batch[i].program, batch[i + 1].program, rng)
        if out is None:
            continue
        spliced_any = True
        span = _harness_body_span(out)
        assert span is not None
        tlo, thi = span
        for row in np.asarray(out):
            if OPCODES[int(row[0])].imm == "target":
                assert tlo <= int(row[4]) < max(thi, tlo + 1), row
    assert spliced_any


def test_spliced_scenarios_stay_differentially_clean():
    """Splice mutants are real fuzz inputs: a batch of pool-spliced
    random scenarios must replay with zero differential/invariant
    problems on the map mode."""
    pool = generate_batch(16, 88)
    randoms = [s for s in pool if s.kind == "random"]
    rng = np.random.default_rng(6)
    mutants, spliced = [], 0
    for s in randoms:
        m = mutate_scenario(s, rng, n_mutations=2, pool=pool)
        spliced += not np.array_equal(m.program, s.program)
        mutants.append(m)
    report = fuzz(mutants, modes=("map",))
    assert report.ok, report.summary()
    assert spliced >= 1  # the splice op actually fires with a pool


def test_mutate_without_pool_never_touches_the_program():
    """The historical contract stands: without a donor pool there is no
    splice op, so mutation leaves the program bytes alone."""
    batch = generate_batch(8, 99)
    rng = np.random.default_rng(2)
    for s in batch:
        for _ in range(6):
            m = mutate_scenario(s, rng, n_mutations=3)
            assert np.array_equal(m.program, s.program)


# ---------------------------------------------------------------------------
# Coverage: static fault counts in the signature
# ---------------------------------------------------------------------------

def test_coverage_signature_separates_faulted_twins():
    from repro.sim.check import case_signature
    from repro.sim.check.coverage import fault_counts
    rng = np.random.default_rng(21)
    s = generate_batch(4, 55)[0]
    twin = with_fault_schedule(s, rng)
    assert fault_counts(s) == (0, 0, 0)
    pre, spur, ab = fault_counts(twin)
    assert pre + spur + ab >= 1
    zeros = np.zeros(8)
    sig_a = case_signature(s, zeros, zeros, zeros, 0, 0, 0, "halted")
    sig_b = case_signature(twin, zeros, zeros, zeros, 0, 0, 0, "halted")
    assert sig_a != sig_b
    assert sig_a[-1] != sig_b[-1]    # the static fault element separates
    assert sig_a[2:-1] == sig_b[2:-1]  # histogram elements are untouched


def test_coverage_map_accumulates_fault_totals(fault_batch):
    from repro.sim.check import CoverageMap, run_batch_oracle
    cov = CoverageMap()
    sub = fault_batch[:6]
    res = run_batch_oracle(sub, collect_trace=True, collect_coverage=True)
    cov.add_batch(sub, res)
    rep = cov.report()
    totals = rep["scheduled_faults"]
    assert totals.get("fault_cases") == len(sub)
    assert sum(totals.get(k, 0)
               for k in ("preempt", "spurious", "abort")) >= len(sub)
