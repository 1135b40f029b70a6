"""Differential fuzzer end-to-end: generator well-formedness, oracle vs
run_sweep bit-equality across all three sweep drivers, invariants on composed
scenarios, and the mutation self-test (an injected store-visibility engine
bug must be caught and shrunk to a dozen instructions or fewer)."""

import numpy as np
import pytest

from repro.sim import Layout, read_collision_counters
from repro.sim.check import (PAD_MEM_WORDS, PAD_THREADS, case_problems,
                             count_instructions, failure_classes, fuzz,
                             generate_batch, load_scenario, save_scenario,
                             shrink)
from repro.sim.check.generate import ADDR_REGS, DATA_REGS
from repro.sim.isa import ADDI, HASH, MOVI, N_OPS, OPCODES, R_AT, R_LIDX, \
    R_NX
from repro.sim.programs import PROG_LEN

BATCH_SEED = 123
N_CASES = 24  # 14 composed (ALL of SIM_LOCKS, round-robin) + 10 random


@pytest.fixture(scope="module")
def batch():
    return generate_batch(N_CASES, BATCH_SEED)


def test_generate_batch_is_deterministic_and_padded(batch):
    again = generate_batch(N_CASES, BATCH_SEED)
    for a, b in zip(batch, again):
        assert np.array_equal(a.program, b.program)
        assert a.seed == b.seed and a.horizon == b.horizon
    other = generate_batch(N_CASES, BATCH_SEED + 1)
    assert any(not np.array_equal(a.program, b.program)
               for a, b in zip(batch, other))
    for s in batch:
        assert s.program.shape == (PROG_LEN, 5)
        assert s.init_pc.shape == (PAD_THREADS,)
        assert s.init_mem.shape == (PAD_MEM_WORDS,)
        assert 1 <= s.n_active <= PAD_THREADS
    from repro.sim import SIM_LOCKS
    locks = {s.lock for s in batch if s.kind == "composed"}
    assert locks == set(SIM_LOCKS)  # round-robin covers the full lock table
    assert any(s.kind == "random" for s in batch)


def test_random_programs_are_well_formed(batch):
    """Structural well-formedness from the OPCODES metadata table: opcodes
    valid, branch targets in range, random writes confined to data
    registers, ACQ/REL lock indices pinned to the valid register."""
    for s in batch:
        if s.kind != "random":
            continue
        prog = np.asarray(s.program)
        for op, a, b, c, imm in prog:
            info = OPCODES[int(op)]
            assert 0 <= op < N_OPS
            if info.imm == "target":
                assert 0 <= imm < PROG_LEN
            if info.a == "rdst":
                assert a in DATA_REGS + (R_AT, R_NX)
                if a == R_AT:
                    assert op == HASH  # only HASH may write an address reg
                if a == R_NX:
                    assert op in (MOVI, ADDI)  # the guaranteed-HALT harness
            if info.a == "lidx":
                assert a == R_LIDX
            if info.b == "lidx":
                assert b == R_LIDX
            for role, val in ((info.a, a), (info.b, b)):
                if role == "raddr":
                    assert val in ADDR_REGS


def test_fuzz_batch_differential_and_invariants(batch):
    """The acceptance sweep in miniature: oracle stats == run_sweep stats
    bit-identically across map/vmap/sched, and every invariant
    holds."""
    report = fuzz(batch)
    assert report.ok, report.summary()
    assert report.total_events > 0


def test_injected_store_visibility_bug_is_caught_and_shrunk(batch):
    """Mutation test on store visibility (the acceptance criterion): making
    stores eagerly visible must produce oracle/engine divergence, and the
    shrinker must reduce a failing case to <= 12 instructions that still
    witness the bug and are clean without it."""
    report = fuzz(batch, modes=("map",), oracle_mutate=("eager_store",))
    assert not report.ok, "eager_store mutation was not caught"
    _idx, scenario, problems = report.failures[0]
    assert "differential" in failure_classes(problems)
    shrunk = shrink(scenario, modes=("map",),
                    oracle_mutate=("eager_store",))
    assert count_instructions(shrunk.program) <= 12
    # still witnesses the bug ...
    still = case_problems(shrunk, modes=("map",),
                          oracle_mutate=("eager_store",))
    assert "differential" in failure_classes(still)
    # ... and the differential is clean on the real engine/oracle pair
    clean = case_problems(shrunk, modes=("map",))
    assert "differential" not in failure_classes(clean)


def test_lost_wake_and_free_invalidation_mutations_are_caught(batch):
    for mutation in ("lost_wake", "free_invalidation"):
        report = fuzz(batch, modes=("map",), oracle_mutate=(mutation,))
        assert not report.ok, f"{mutation} mutation was not caught"


def test_scenario_corpus_roundtrip(tmp_path, batch):
    path = tmp_path / "case.npz"
    save_scenario(path, batch[0], note="roundtrip")
    loaded = load_scenario(path)
    assert np.array_equal(loaded.program, batch[0].program)
    assert np.array_equal(loaded.init_mem, batch[0].init_mem)
    assert loaded.meta == batch[0].meta
    assert loaded.horizon == batch[0].horizon
    assert loaded.lock == batch[0].lock


def test_sched_geometry_varies_across_a_fuzz_batch():
    """Regression: the fuzz batch used to run mode="sched" only at the
    default lanes=4/chunk=512 point, so the lane scheduler's refill/edge
    paths were never inside the differential.  The per-case draws must be
    deterministic in the seed, cover several distinct geometries, and
    include the chunk=1 and lanes>sub-batch edges."""
    from repro.sim.check import SCHED_GEOMETRY_POOL, sched_geometries
    geoms = sched_geometries(32, seed=11)
    assert geoms == sched_geometries(32, seed=11)       # deterministic
    assert geoms != sched_geometries(32, seed=12)       # seed-sensitive
    assert set(geoms) <= set(SCHED_GEOMETRY_POOL)
    assert len(set(geoms)) >= 3                         # actually varies
    assert any(chunk == 1 for _, chunk in geoms)        # chunk=1 edge
    # the B < lanes edge: at least one drawn geometry has more lanes than
    # the number of cases assigned to it in a small batch
    small = sched_geometries(6, seed=11)
    counts = {g: small.count(g) for g in set(small)}
    assert any(lanes > counts[(lanes, chunk)]
               for (lanes, chunk) in counts), counts


def test_sched_randomized_geometry_matches_map(batch):
    """Randomized lane placement must not change any stat: sched results
    (grouped by drawn geometry) stay bit-identical to the sequential map
    driver for every case."""
    from repro.sim.check import run_engine_batch
    sub = batch[:6]
    ref = run_engine_batch(sub, "map")
    for sched_seed in (0, 9):
        got = run_engine_batch(sub, "sched", sched_seed=sched_seed)
        for r, g in zip(ref, got):
            for k in ("acquisitions", "events", "grant_value"):
                assert np.array_equal(r[k], g[k]), (sched_seed, k)


def test_sched_geometry_is_pinned_into_scenarios_for_replay(batch, tmp_path):
    """A geometry-dependent failure must be reproducible from its own
    artifact: fuzz() stamps each case's drawn (lanes, chunk) into the
    scenario meta, a pinned geometry survives re-stamping under a
    different seed, and the corpus roundtrip keeps the pin."""
    from repro.sim.check import SCHED_GEOMETRY_POOL
    from repro.sim.check.runner import stamp_sched_geometry
    stamped = stamp_sched_geometry(batch[:4], sched_seed=3)
    pins = [s.meta["sched_geometry"] for s in stamped]
    assert all(tuple(p) in set(SCHED_GEOMETRY_POOL) for p in pins)
    again = stamp_sched_geometry(stamped, sched_seed=99)
    assert [s.meta["sched_geometry"] for s in again] == pins
    path = tmp_path / "pinned.npz"
    save_scenario(path, stamped[0])
    assert load_scenario(path).meta["sched_geometry"] == pins[0]


def test_artifact_with_a_pallas_chunk_replays(batch, tmp_path):
    """Artifacts saved while the checker also ran a burst-chunked kernel
    driver carry ``meta["pallas_chunk"]``; they still load and replay
    clean under every mode, the key ignored."""
    from repro.sim.check import MODES
    s = batch[0]
    path = tmp_path / "old.npz"
    save_scenario(path, s.replace(meta={**s.meta, "pallas_chunk": 16}))
    loaded = load_scenario(path)
    assert loaded.meta["pallas_chunk"] == 16
    assert case_problems(loaded, modes=MODES) == []


def test_cli_rejects_an_unknown_mode(capsys):
    """``--modes`` names drivers; an unknown one is a usage error (exit
    code 2, argparse's), not a traceback from the engine."""
    from repro.sim.check.__main__ import main
    with pytest.raises(SystemExit) as exc:
        main(["--modes", "map,pallas", "--cases", "1"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "pallas" in err and "map, vmap, sched" in err


def test_liveness_checker_convicts_a_starving_lock():
    """Self-test for the liveness bound: a ticket lock whose release
    occasionally skips a grant strands one waiter while the rest keep
    cycling — progress and deadlock checks both pass (the run is cut by
    the horizon with plenty of global progress), so without the liveness
    bound this starvation was invisible."""
    from repro.sim.check.make_corpus import starving_ticket_scenario
    rng = np.random.default_rng(5)
    convicted = witnessed_alive = 0
    for _ in range(8):
        s = starving_ticket_scenario(rng)
        got = failure_classes(case_problems(s, modes=()))
        if "liveness" in got:
            convicted += 1
            # the interesting witnesses: starving while NOT deadlocked and
            # with global progress intact — invisible to every other check
            if "deadlock" not in got and "progress" not in got:
                witnessed_alive += 1
    assert convicted >= 6, convicted       # the checker catches the starver
    assert witnessed_alive >= 1            # ... including live-but-starving


def test_fair_locks_pass_the_liveness_bound(batch):
    """The bound must not convict a correct FIFO lock: every composed
    scenario in the deterministic batch replays with zero liveness
    problems (already implied by the full-batch fuzz, pinned here against
    the invariant in isolation)."""
    from repro.sim.check import run_oracle_case
    from repro.sim.check.invariants import check_liveness
    checked = 0
    for s in batch:
        if s.kind != "composed" or not s.meta.get("ticket_fifo"):
            continue
        _out, trace = run_oracle_case(s)
        assert check_liveness(s, trace) == [], s.lock
        checked += 1
    assert checked >= 5


def test_near_wrap_tickets_stay_clean():
    """Regression for int32 ticket wrap: a twa-sem (SPIN_GE frontier) and
    a plain ticket case seeded two draws below INT32_MAX must cross the
    wrap mid-run with zero differential or invariant problems.  Before the
    wrap-safe SPIN_GE compare, the semaphore admitted entrants past the
    permit cap as soon as post-wrap (negative) tickets met a still-positive
    grant."""
    from repro.sim.check import gen_composed_scenario
    from repro.sim.check.generate import INT32_MAX
    from repro.sim.isa import OFF_TICKET
    rng = np.random.default_rng(17)
    for lock in ("ticket", "twa-sem"):
        wrapped = False
        for _ in range(12):
            s = gen_composed_scenario(rng, lock, n_locks=1,
                                      ticket_base=INT32_MAX - 2)
            assert case_problems(s, modes=("map",)) == []
            from repro.sim.check import run_oracle_case
            out, _ = run_oracle_case(s)
            if int(np.asarray(out["grant_value"])[OFF_TICKET]) < 0:
                wrapped = True
                break
        assert wrapped, f"{lock}: no case crossed the wrap"


def test_read_collision_counters_requires_the_flag():
    """A sweep run without count_collisions=True leaves queue-lock state in
    the node words; reading it as counters must be a loud error, not
    garbage."""
    layout = Layout(n_threads=4, n_locks=1)
    with pytest.raises(ValueError, match="count_collisions"):
        read_collision_counters(np.zeros(layout.mem_words, np.int32),
                                layout)
    flagged = Layout(n_threads=4, n_locks=1, count_collisions=True)
    wakes, futile = read_collision_counters(
        np.zeros(flagged.mem_words, np.int32), flagged)
    assert wakes.shape == futile.shape == (4,)
