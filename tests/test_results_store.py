"""Results store + lock advisor + per-acquisition latency percentiles +
outside_work axis: the PR-9 subsystem end to end.

Covers: latency-histogram bit-identity across all three engine drivers and
both batch-oracle implementations (including a wrap-adjacent ticket
case), percentile extraction semantics, the outside_work axis
(reachability + throughput monotonicity), store round-trip / coordinate
validation / v0 migration, advisor exact/nearest/empty resolution, and
the shrinker's fault-schedule minimization passes.
"""

import json

import numpy as np
import pytest

from repro.sim.costs import DEFAULT_COSTS
from repro.sim.check.generate import (GRANT_WORD_LOCKS, PAD_MEM_WORDS,
                                      PAD_THREADS, Scenario,
                                      TICKET_FIFO_LOCKS)
from repro.sim.check.runner import (case_problems, failure_classes, fuzz,
                                    run_oracle_case, shrink)
from repro.sim.faults import F_PREEMPT, F_SPURIOUS
from repro.sim.isa import OFF_GRANT, OFF_TICKET, TSTART
from repro.sim.programs import (INIT_MEM_GEN, Layout, build_mutexbench,
                                init_state, pad_mem, pad_program,
                                pad_threads)
from repro.sim.results import (COORD_KEYS, ResultsStore, SCHEMA_VERSION,
                               migrate, recommend_lock, row_from_result)
from repro.sim.workloads import (SweepSpec, hist_percentile,
                                 latency_percentiles, run_sweep)


def _latency_scenario(lock: str, *, seed: int = 7, ticket_base: int = 0,
                      outside_work: int = 5) -> Scenario:
    layout = Layout(n_threads=8, n_locks=1, wa_size=64)
    prog = build_mutexbench(lock, layout, cs_work=3, ncs_max=20,
                            outside_work=outside_work, collect_latency=True)
    pc, regs = init_state(layout)
    pc, regs = pad_threads(pc, regs, PAD_THREADS)
    gen_mem = INIT_MEM_GEN.get(lock)
    init_mem = (gen_mem(layout) if gen_mem
                else np.zeros(layout.mem_words, np.int32))
    if ticket_base:
        init_mem[OFF_TICKET] = ticket_base
        init_mem[OFF_GRANT] = ticket_base
    return Scenario(
        kind="composed", lock=lock, program=pad_program(prog),
        init_pc=pc, init_regs=regs,
        init_mem=pad_mem(init_mem, PAD_MEM_WORDS),
        n_active=8, wa_base=layout.wa_base, wa_size=layout.wa_size,
        horizon=30_000, max_events=60_000, seed=seed,
        costs=DEFAULT_COSTS.to_array(),
        meta={"cap": 1, "probed": False, "rw": False, "fissile": False,
              "count_collisions": False,
              "ticket_fifo": lock in TICKET_FIFO_LOCKS,
              "grant_word": lock in GRANT_WORD_LOCKS,
              "ticket_base": ticket_base,
              "layout": {"n_threads": 8, "n_locks": 1, "wa_size": 64,
                         "private_arrays": False, "long_term_threshold": 1,
                         "sem_permits": 4, "reader_fraction": 50,
                         "count_collisions": False, "timo_patience": 24}})


# ---------------------------------------------------------------------------
# Latency histogram: bit-identity + semantics
# ---------------------------------------------------------------------------

def test_lat_hist_bit_identical_across_modes_and_oracles():
    """lat_hist is in STAT_KEYS, so the differential harness enforces it:
    TSTART-instrumented programs must agree across map/vmap/sched
    AND both batch-oracle implementations (NumPy and the C kernel),
    including a ticket lock seeded wrap-adjacent so the (now - t0) window
    spans tickets crossing the int32 wrap."""
    scens = [_latency_scenario(lock)
             for lock in ("ticket", "twa", "mcs", "anderson")]
    scens.append(_latency_scenario("ticket", seed=9,
                                   ticket_base=2**31 - 8))
    report = fuzz(scens)
    assert report.ok, report.summary()
    report_b = fuzz(scens, modes=("map",), batch_oracle=True)
    assert report_b.ok, report_b.summary()
    # and the instrumentation actually sampled: one entry per acquisition
    for s in scens:
        out, _ = run_oracle_case(s)
        assert out["lat_hist"].sum() == out["acquisitions"].sum() > 0, s.lock


def test_uninstrumented_programs_accumulate_no_histogram():
    s = _latency_scenario("ticket")
    prog = build_mutexbench("ticket", Layout(n_threads=8, n_locks=1,
                                             wa_size=64),
                            cs_work=3, ncs_max=20, collect_latency=False)
    assert not (np.asarray(prog)[:, 0] == TSTART).any()
    out, _ = run_oracle_case(s.replace(program=pad_program(prog)))
    assert out["lat_hist"].sum() == 0
    assert out["acquisitions"].sum() > 0


def test_hist_percentile_bucket_semantics():
    hist = np.zeros(32, np.int32)
    hist[0] = 50          # 50 samples at exactly 0
    hist[5] = 49          # 49 samples in [16, 32)
    hist[10] = 1          # the single tail sample in [512, 1024)
    assert hist_percentile(hist, 0.5) == 0.0
    assert hist_percentile(hist, 0.99) == 31.0    # bucket 5 upper edge
    assert hist_percentile(hist, 0.999) == 1023.0  # bucket 10 upper edge
    assert np.isnan(hist_percentile(np.zeros(32), 0.5))


def test_latency_percentiles_raises_without_collection():
    spec = SweepSpec(locks="ticket", threads=2, seeds=1, horizon=20_000,
                     max_events=50_000)
    res = run_sweep(spec)[0]
    assert "lat_hist" not in res
    with pytest.raises(ValueError, match="collect_latency"):
        latency_percentiles(res)


def test_run_sweep_latency_columns():
    spec = SweepSpec(locks=("ticket", "twa"), threads=4, seeds=1,
                     cs_work=2, ncs_max=20, horizon=40_000,
                     max_events=100_000, collect_latency=True)
    for res in run_sweep(spec):
        total = int(res["lat_hist"].sum())
        assert total == int(res["acquisitions"].sum()) > 0
        assert res["lat_p50"] <= res["lat_p99"] <= res["lat_p999"]
        assert latency_percentiles(res) == (res["lat_p50"], res["lat_p99"],
                                            res["lat_p999"])


# ---------------------------------------------------------------------------
# outside_work axis
# ---------------------------------------------------------------------------

def test_outside_work_reaches_the_program_and_slows_throughput():
    spec = SweepSpec(locks="ticket", threads=4, seeds=1, cs_work=2,
                     outside_work=(0, 40, 400), ncs_max=20,
                     horizon=60_000, max_events=150_000)
    res = run_sweep(spec)
    by_ow = {r["outside_work"]: r for r in res}
    assert set(by_ow) == {0, 40, 400}
    for r in res:
        assert int(r["acquisitions"].sum()) > 0, "outside_work starved runs"
    # a fixed off-lock delay strictly bounds the arrival rate: more
    # outside work can never speed the lock up
    assert (by_ow[0]["throughput"] >= by_ow[40]["throughput"]
            >= by_ow[400]["throughput"])
    assert by_ow[0]["throughput"] > by_ow[400]["throughput"]


def test_outside_work_zero_is_byte_identical_to_legacy_programs():
    layout = Layout(n_threads=4, n_locks=1)
    legacy = build_mutexbench("twa", layout, cs_work=4, ncs_max=100)
    explicit = build_mutexbench("twa", layout, cs_work=4, ncs_max=100,
                                outside_work=0, collect_latency=False)
    assert np.array_equal(legacy, explicit)


# ---------------------------------------------------------------------------
# Results store: round-trip, validation, migration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_rows():
    spec = SweepSpec(locks=("ticket", "twa"), threads=(2, 4), seeds=(1, 2),
                     cs_work=2, outside_work=(0, 10), ncs_max=20,
                     horizon=30_000, max_events=80_000,
                     collect_latency=True)
    return run_sweep(spec)


def test_store_roundtrip(tmp_path, sweep_rows):
    store = ResultsStore(tmp_path / "r.jsonl")
    assert store.append_sweep(sweep_rows) == len(sweep_rows)
    rows = store.load()
    assert len(rows) == len(sweep_rows)
    for res, row in zip(sweep_rows, rows):
        assert row["schema_version"] == SCHEMA_VERSION
        for key in ("lock", "n_threads", "seed", "cs_work", "outside_work"):
            assert row[key] == res[key]
        assert row["throughput"] == res["throughput"]
        assert row["acquisitions"] == int(res["acquisitions"].sum())
        assert row["lat_p50"] == res["lat_p50"]
    # query filters on coordinates
    sub = store.query(lock="twa", outside_work=10)
    assert sub and all(r["lock"] == "twa" and r["outside_work"] == 10
                       for r in sub)
    with pytest.raises(ValueError, match="non-coordinate"):
        store.query(throughput=1.0)


def test_store_rejects_incomplete_coordinates(tmp_path, sweep_rows):
    store = ResultsStore(tmp_path / "r.jsonl")
    row = row_from_result(sweep_rows[0])
    bad = {k: v for k, v in row.items() if k != "outside_work"}
    with pytest.raises(ValueError, match="outside_work"):
        store.append_rows([bad])
    with pytest.raises(ValueError, match="unknown keys"):
        store.append_rows([{**row, "vibes": 11}])
    # a rejected batch must leave the store untouched, not half-written
    with pytest.raises(ValueError):
        store.append_rows([row, bad])
    assert len(store) == 0


def test_store_env_hook_persists_sweeps(tmp_path, monkeypatch):
    from repro.sim.workloads import RESULTS_STORE_ENV
    path = tmp_path / "hook.jsonl"
    monkeypatch.setenv(RESULTS_STORE_ENV, str(path))
    spec = SweepSpec(locks="ticket", threads=2, seeds=(1, 2),
                     horizon=20_000, max_events=50_000)
    run_sweep(spec)
    rows = ResultsStore(path).load()
    assert len(rows) == 2
    assert rows[0]["lock"] == "ticket"
    assert rows[0]["lat_hist"] is None   # collect_latency was off


def test_migrate_upgrades_synthetic_v0_rows(tmp_path):
    v0 = {  # a pre-versioning row: no stamp, no outside_work, no latency
        "lock": "twa", "n_threads": 8, "seed": 1, "cs_work": 4,
        "private_arrays": False, "wa_size": 4096,
        "long_term_threshold": 1, "sem_permits": 4, "reader_fraction": 50,
        "n_locks": 1, "horizon": 100_000, "costs": [1] * 9,
        "throughput": 0.01, "avg_handover": 100.0, "acquisitions": 1000,
        "waited_acquisitions": 900, "events": 5000, "sleeping": 0,
    }
    path = tmp_path / "old.jsonl"
    path.write_text(json.dumps(v0) + "\n")
    store = ResultsStore(path)
    row = store.load()[0]
    assert row["schema_version"] == SCHEMA_VERSION
    assert row["outside_work"] == 0          # v0 measured the ow=0 point
    assert row["preempt_faults"] == 0
    assert row["mode"] == "unknown"
    assert row["lat_p50"] is None            # unmeasured, not fabricated
    store.validate_row(row)                  # migrated rows are writable
    # migrate() refuses rows newer than this checkout
    with pytest.raises(ValueError, match="newer"):
        migrate({**row, "schema_version": SCHEMA_VERSION + 1})
    # and rows that cannot be located in workload space
    with pytest.raises(ValueError, match="lock"):
        migrate({"throughput": 1.0})
    # rewrite persists the upgrade
    store.rewrite()
    raw = json.loads(path.read_text().splitlines()[0])
    assert raw["schema_version"] == SCHEMA_VERSION


# ---------------------------------------------------------------------------
# Advisor
# ---------------------------------------------------------------------------

def test_advisor_exact_nearest_and_empty(tmp_path, sweep_rows):
    store = ResultsStore(tmp_path / "r.jsonl")
    store.append_sweep(sweep_rows)

    exact = recommend_lock(store, {"n_threads": 4, "cs_work": 2,
                                   "outside_work": 10})
    assert exact["confidence"] == "exact"
    assert exact["lock"] in ("ticket", "twa")
    assert exact["n_threads"] == 4
    # the recommendation is the measured argmax at that point
    measured = [r for r in store.load()
                if r["n_threads"] == 4 and r["cs_work"] == 2
                and r["outside_work"] == 10]
    best = {}
    for r in measured:
        best.setdefault(r["lock"], []).append(r["throughput"])
    want = max(best, key=lambda lk: float(np.median(best[lk])))
    assert exact["lock"] == want

    near = recommend_lock(store, {"n_threads": 3, "cs_work": 3})
    assert near["confidence"] == "nearest"
    assert near["matched"]["n_threads"] in (2, 4)   # snapped to a bin
    assert near["n_threads"] == near["matched"]["n_threads"]

    free = recommend_lock(store, {"cs_work": 2})    # threads left free
    assert free["n_threads"] in (2, 4)

    with pytest.raises(ValueError, match="unknown workload keys"):
        recommend_lock(store, {"horizon": 1})
    with pytest.raises(ValueError, match="empty"):
        recommend_lock(ResultsStore(tmp_path / "none.jsonl"),
                       {"n_threads": 4})


def test_advisor_cli_transcript(tmp_path, sweep_rows, capsys):
    from repro.sim.results.__main__ import main
    path = tmp_path / "r.jsonl"
    ResultsStore(path).append_sweep(sweep_rows)
    main(["--store", str(path), "recommend", "--threads", "4",
          "--cs-work", "2", "--outside-work", "10"])
    out = capsys.readouterr().out
    assert "recommend:" in out and "confidence: exact" in out
    main(["--store", str(path), "summary"])
    out = capsys.readouterr().out
    assert f"rows:    {len(sweep_rows)}" in out


# ---------------------------------------------------------------------------
# Shrinker: fault-schedule minimization
# ---------------------------------------------------------------------------

def test_shrink_minimizes_fault_schedules():
    """A failure that depends on fault injection (the dropped_fault oracle
    mutation only diverges while applied fault rows remain) must shrink to
    a smaller schedule, never to an empty one, with preemption stall
    widths halved toward minimal.  Rows 1-2 are scheduled past the run's
    last event, so they never fire and must be dropped; row 0 is the one
    fault that matters."""
    base = _latency_scenario("ticket")
    dead = base.max_events - 1  # far past the ~4k events the run executes
    rows = [[F_PREEMPT, 40, 0, 2048],
            [F_PREEMPT, dead - 1, 1, 64],
            [F_SPURIOUS, dead, 2, 0]]
    scenario = base.replace(meta={**base.meta, "faults": rows})
    assert failure_classes(case_problems(
        scenario, oracle_mutate=("dropped_fault",))) == {"differential"}
    small = shrink(scenario, modes=("map",),
                   oracle_mutate=("dropped_fault",), program_passes=False)
    after = [list(r) for r in (small.meta.get("faults") or [])]
    assert after == [[F_PREEMPT, 40, 0, after[0][3]]], (rows, after)
    assert 1 <= after[0][3] <= 2048  # stall width halved, never grown
    assert failure_classes(case_problems(
        small, oracle_mutate=("dropped_fault",))) == {"differential"}
    # and the other passes still ran: the repro got cheaper too
    assert small.horizon < scenario.horizon


def test_shrink_drops_irrelevant_faults_entirely():
    """When the failure is fault-independent (an always-on differential
    mutation, applied identically on both sides), the fault rows are pure
    noise and the shrinker must delete the whole schedule."""
    base = _latency_scenario("ticket")
    rows = [[F_PREEMPT, 40, 0, 256], [F_SPURIOUS, 90, 2, 0]]
    scenario = base.replace(meta={**base.meta, "faults": rows})
    classes = failure_classes(case_problems(
        scenario, oracle_mutate=("free_invalidation",)))
    assert "differential" in classes
    small = shrink(scenario, modes=("map",),
                   oracle_mutate=("free_invalidation",), program_passes=False)
    assert not small.meta.get("faults"), small.meta.get("faults")
