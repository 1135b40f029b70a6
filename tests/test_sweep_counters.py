"""The sweep drivers' lane-step counter and the sweep's host spans.

Every driver counts the step iterations its loop ran; ``engine.run_sweep``
reports them in ``pad_stats`` as ``lanes`` and ``lane_steps``, the
denominator of the share of lane-steps that ran an event. The counter reads
nothing of the simulated state, so the results stay bit-identical across
drivers. ``workloads.run_sweep`` and ``engine.run_sweep`` mark their host
phases with ``lockvm.*`` spans on the profiler's clock.
"""

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.sim import SweepSpec, engine
from repro.sim.engine import OUT_KEYS
from repro.sim.programs import PROG_LEN
from repro.sim.workloads import pack_engine_cells, run_sweep

# one heavy cell over light ones, and a cell that never runs (horizon 0)
CELLS = [("twa", 6, 15_000), ("ticket", 2, 1_200), ("mcs", 3, 1_200),
         ("ticket", 5, 2_000), ("twa", 2, 800), ("anderson", 4, 1_500),
         ("ticket", 3, 0), ("twa", 4, 2_500)]
DRIVERS = {
    "map": {},
    "vmap": {},
    "sched": {"lanes": 3, "chunk": 128},
    "sched_one": {"lanes": 1, "chunk": 1},
    "sched_wide": {"lanes": 32, "chunk": 64},   # more lanes than cells
}


@pytest.fixture(scope="module")
def sweeps():
    programs, kw = pack_engine_cells(CELLS, ncs_max=100, seeds=5)
    return {name: engine.run_sweep(programs, mode=name.split("_")[0],
                                   **geometry, **kw)
            for name, geometry in DRIVERS.items()}


@pytest.mark.parametrize("name", sorted(DRIVERS))
def test_every_driver_reports_lanes_and_lane_steps_and_the_same_rows(
        sweeps, name):
    out, ref = sweeps[name], sweeps["map"]
    ps = out["pad_stats"]
    assert {"lanes", "lane_steps"} <= set(ps)
    assert ps["sum_events"] <= ps["lane_steps"]
    assert "loop_iters" not in out
    for key in OUT_KEYS:
        assert np.array_equal(ref[key], out[key]), (name, key)


def test_map_lane_steps_are_the_events(sweeps):
    ps = sweeps["map"]["pad_stats"]
    assert (ps["lanes"], ps["lane_steps"]) == (1, ps["sum_events"])


def test_vmap_lane_steps_are_the_longest_cell_times_the_batch(sweeps):
    ps = sweeps["vmap"]["pad_stats"]
    assert ps["lanes"] == len(CELLS)
    assert ps["lane_steps"] == ps["max_events"] * len(CELLS)


@pytest.mark.parametrize("name", ["sched", "sched_wide"])
def test_sched_lane_steps_are_whole_bursts_of_every_lane(sweeps, name):
    geometry = DRIVERS[name]
    ps = sweeps[name]["pad_stats"]
    lanes = min(geometry["lanes"], len(CELLS))
    assert ps["lanes"] == lanes
    assert ps["lane_steps"] % (geometry["chunk"] * lanes) == 0
    assert ps["lane_steps"] >= ps["sum_events"]


def test_sched_one_lane_one_step_bursts_pay_one_step_per_event(sweeps):
    """Each cell that runs takes one burst per event; the zero-horizon cell
    takes the one burst that finds it finished."""
    out = sweeps["sched_one"]
    ps = out["pad_stats"]
    never_ran = int((out["events"] == 0).sum())
    assert never_ran == 1
    assert ps["lane_steps"] == ps["sum_events"] + never_ran


def test_lane_steps_are_exact_on_a_fault_free_spec_sweep():
    spec = SweepSpec(locks=("ticket", "twa"), threads=(1, 3), seeds=(1, 2),
                     horizon=3_000)
    rows = {mode: run_sweep(spec, mode=mode) for mode in ("map", "vmap")}
    for mode, expect in (("map", "sum_events"), ("vmap", "max_events")):
        ps = rows[mode][0]["pad_stats"]
        lanes = 1 if mode == "map" else len(rows[mode])
        assert ps["lane_steps"] == ps[expect] * lanes, mode
    for a, b in zip(rows["map"], rows["vmap"]):
        assert np.array_equal(a["acquisitions"], b["acquisitions"])
        assert np.array_equal(a["mem"], b["mem"])


def test_each_driver_compiles_under_a_name_of_its_own():
    n_threads, mem_words = 4, 64 * 4
    drivers = {
        "lockvm_cell": engine._make_run(n_threads, mem_words, 1),
        "lockvm_map": engine._make_run_map(n_threads, mem_words, 1),
        "lockvm_vmap": engine._make_run_batched(n_threads, mem_words, 1),
        "lockvm_sched": engine._make_run_sched(n_threads, mem_words, 1, 2, 8),
    }
    for name, fn in drivers.items():
        assert fn.__name__ == name
        assert jax.jit(fn).__name__ == name


def _host_spans(trace_dir) -> list[tuple]:
    """``(start, end, name, args)`` of the ``lockvm.*`` host events."""
    (xplane,) = Path(trace_dir).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(xplane))
    return sorted(((e.start_ns, e.end_ns, e.name.split("#")[0], dict(e.stats))
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("lockvm.")), key=lambda e: e[0])


def test_a_traced_sweep_marks_its_host_phases_in_order(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RESULTS_STORE", str(tmp_path / "store.jsonl"))
    spec = SweepSpec(locks=("ticket", "twa"), threads=(1, 3), seeds=(1, 2),
                     horizon=2_000)
    run_sweep(spec, mode="sched", lanes=2, chunk=64)   # compile untraced
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        rows = run_sweep(spec, mode="sched", lanes=2, chunk=64)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path / "trace")
    assert [n for *_, n, _ in spans] == [
        "lockvm.sweep", "lockvm.build", "lockvm.pack", "lockvm.dispatch",
        "lockvm.readback", "lockvm.assemble", "lockvm.assemble",
        "lockvm.store"]
    (s0, e0, _, sweep_args), *inner = spans
    assert sweep_args == {"cells": 8, "mode": "sched"}
    assert all(s0 <= s <= e <= e0 for s, e, *_ in inner)
    # siblings, one after another
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
    mem_words = max(r["layout"].mem_words for r in rows)
    assert inner[2][3] == {"mode": "sched", "cells": 8, "n_threads": 3,
                           "mem_words": mem_words, "prog_len": PROG_LEN,
                           "lanes": 2, "chunk": 64, "n_faults": 0,
                           "n_locks": 1, "mem_form": "index",
                           "queue": "longest_first"}
    ps = rows[0]["pad_stats"]
    assert inner[4][3] == {"lanes": 2, "lane_steps": ps["lane_steps"]}
