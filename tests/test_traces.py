"""Trace→program pipeline: quantization determinism + monotonicity, the
trace-compiled sweep (workload provenance in the results store), the
advisor loop back into ServeEngine(lock="auto"), schema-v2 migration, and
the differential gate on trace scenarios."""

import json
import os

import numpy as np
import pytest

from repro.serve.trace import LockTrace
from repro.sim.programs import Layout
from repro.sim.results import ResultsStore, SCHEMA_VERSION, recommend_lock
from repro.sim.traces import (quantize_trace, trace_layout_for,
                              trace_sweep_spec, trace_workload_coords,
                              workload_from_meta)
from repro.sim.workloads import RESULTS_STORE_ENV, run_sweep

SWEEP_LOCKS = ("ticket", "twa", "mcs")


def _mk_trace(scale: float = 1.0, n: int = 24, n_reads: int = 8,
              seed: int = 0) -> LockTrace:
    rng = np.random.default_rng(seed)
    arrival = np.sort(rng.uniform(0.0, 1.0, n))
    grant = arrival + rng.uniform(0.0, 0.02, n)
    release = grant + rng.uniform(0.01, 0.06, n)
    return LockTrace(arrival_s=arrival * scale, grant_s=grant * scale,
                     release_s=release * scale,
                     tickets=np.arange(n, dtype=np.int64),
                     read_s=rng.uniform(0.0, 1.0, n_reads) * scale,
                     lanes=3, name="synth")


# ---------------------------------------------------------------------------
# Quantization properties
# ---------------------------------------------------------------------------

def test_quantize_is_deterministic_and_meta_roundtrips():
    tw1 = quantize_trace(_mk_trace())
    tw2 = quantize_trace(_mk_trace())
    assert tw1 == tw2                       # same trace -> same workload
    assert workload_from_meta(tw1.as_meta()) == tw1
    assert json.loads(json.dumps(tw1.as_meta())) == tw1.as_meta()


def test_quantize_is_monotone_at_fixed_unit():
    """With unit_s pinned, longer recorded durations never compile to less
    work — elementwise over the inverse-CDF tables."""
    base = quantize_trace(_mk_trace(1.0), unit_s=0.004)
    scaled = quantize_trace(_mk_trace(2.0), unit_s=0.004)
    assert all(b <= s for b, s in zip(base.cs_table, scaled.cs_table))
    assert all(b <= s for b, s in zip(base.out_table, scaled.out_table))
    assert scaled.cs_work_rep >= base.cs_work_rep
    # each table is an inverse CDF: nondecreasing in the quantile index
    assert list(base.cs_table) == sorted(base.cs_table)
    assert list(base.out_table) == sorted(base.out_table)


def test_quantize_rejects_empty_and_derives_concurrency():
    with pytest.raises(ValueError, match="empty"):
        quantize_trace(_mk_trace(n=24).__class__(
            arrival_s=np.zeros(0), grant_s=np.zeros(0),
            release_s=np.zeros(0), tickets=np.zeros(0, np.int64),
            read_s=np.zeros(0), lanes=1))
    tw = quantize_trace(_mk_trace())
    assert tw.n_threads >= 1                # peak request concurrency
    assert 0 <= tw.reader_fraction <= 100


def test_trace_layout_appends_past_the_base_layout():
    tw = quantize_trace(_mk_trace(), table_size=8)
    base = Layout(n_threads=4, n_locks=1, wa_size=64)
    lay = trace_layout_for(tw, base)
    assert lay.cs_base >= base.mem_words    # base offsets untouched
    assert lay.mem_words > base.mem_words
    assert lay.mem_words % 16 == 0          # sector aligned


# ---------------------------------------------------------------------------
# Trace-compiled sweep -> store -> advisor -> ServeEngine(lock="auto")
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trace_store(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("store") / "results.jsonl")
    tw = quantize_trace(_mk_trace(), table_size=8, max_steps=24,
                        name="pytest-trace")
    spec = trace_sweep_spec(tw, locks=SWEEP_LOCKS, seeds=(1, 2),
                            horizon=60_000, max_events=150_000)
    os.environ[RESULTS_STORE_ENV] = path
    try:
        rows = run_sweep(spec)
    finally:
        del os.environ[RESULTS_STORE_ENV]
    return path, tw, rows


def test_trace_sweep_rows_carry_workload_provenance(trace_store):
    _, tw, rows = trace_store
    assert {r["lock"] for r in rows} == set(SWEEP_LOCKS)
    coords = trace_workload_coords(tw)
    for r in rows:
        assert r["workload"] == "trace:pytest-trace"
        assert r["throughput"] > 0          # the replay makes progress
        for k, v in coords.items():
            assert r[k] == v                # rows land AT the query point


def test_advisor_closes_the_loop_into_the_engine(trace_store):
    from repro.serve.admission import gate_kind_for_lock
    from repro.serve.engine import ServeEngine
    path, tw, _ = trace_store
    coords = trace_workload_coords(tw)
    rec = recommend_lock(ResultsStore(path), coords)
    assert rec["lock"] in SWEEP_LOCKS
    assert rec["confidence"] == "exact"     # measured at these coordinates
    gate, choice = ServeEngine._make_gate(
        "auto", lanes=2, two_tier=True, threshold=1, store=path,
        workload=coords)
    assert choice["source"] == "advisor"
    assert choice["sim_lock"] == rec["lock"]
    assert gate.kind == gate_kind_for_lock(rec["lock"])


def test_schema_v2_fills_workload_for_v1_rows(trace_store):
    from repro.sim.results import migrate
    path, _, _ = trace_store
    raw = json.loads(open(path).read().splitlines()[0])
    assert raw["schema_version"] == SCHEMA_VERSION
    v1 = {k: v for k, v in raw.items() if k != "workload"}
    v1["schema_version"] = 1
    up = migrate(v1)
    assert up["workload"] == "synthetic"    # every v1 sweep was a grid
    assert up["schema_version"] == SCHEMA_VERSION


# ---------------------------------------------------------------------------
# Differential gate on trace scenarios
# ---------------------------------------------------------------------------

def test_trace_scenarios_are_clean_across_all_modes():
    """Oracle vs map/vmap/sched on trace-compiled scenarios — the
    table-draw programs are under the same bit-identity contract as every
    other generated workload."""
    from repro.sim.check import fuzz
    from repro.sim.check.generate import gen_trace_scenario
    rng = np.random.default_rng(7)
    batch = [gen_trace_scenario(rng, lock)
             for lock in ("ticket", "twa", "mcs", "fissile-twa")]
    assert all(s.meta["workload"] == "trace" for s in batch)
    report = fuzz(batch)
    assert report.ok, report.summary()


def test_trace_fraction_is_deterministic_and_separable():
    from repro.sim.check import generate_batch
    plain = generate_batch(10, 5)
    zero = generate_batch(10, 5, trace_fraction=0.0)
    for a, b in zip(plain, zero):           # 0.0 reproduces history exactly
        assert np.array_equal(a.program, b.program)
    full = generate_batch(10, 5, trace_fraction=1.0)
    assert all(s.meta.get("workload") == "trace" for s in full)
    again = generate_batch(10, 5, trace_fraction=1.0)
    for a, b in zip(full, again):           # same seed -> same trace cases
        assert np.array_equal(a.program, b.program)
        assert np.array_equal(a.init_mem, b.init_mem)
