"""The plain reference agrees with the lockVM where it should, and its
control (one guarantee broken) is caught by the comparison."""

import json

import numpy as np
import pytest

import harness
from conftest import BENCH

ref = harness.load_module(BENCH / "reference" / "lockvm.py", "t_ref_lockvm")
runner = harness.load_module(BENCH / "runners" / "lockvm_sweep.py", "t_runner")
CELLS = [harness.load_cell(p.stem) for p in sorted((BENCH / "workloads").glob("*.json"))]


@pytest.mark.parametrize("lock", sorted(ref.LOCKS))
@pytest.mark.parametrize("n_threads", [1, 8, 64])
def test_reference_programs_match_the_lockvm_builder(lock, n_threads):
    from repro.sim.programs import Layout, build_mutexbench
    for cs_work, ncs_max, latency in ((4, 200, True), (20, 20, False)):
        mine = ref.build_mutexbench(lock, ref.Layout(n_threads, 4096),
                                    cs_work=cs_work, ncs_max=ncs_max,
                                    collect_latency=latency)
        theirs = build_mutexbench(lock, Layout(n_threads=n_threads, n_locks=1),
                                  cs_work=cs_work, ncs_max=ncs_max,
                                  collect_latency=latency)
        np.testing.assert_array_equal(np.asarray(mine, np.int32), theirs)


def small(cell: dict, **sweep) -> dict:
    cell = json.loads(json.dumps(cell))
    cell["config_file"]["sweep"].update(sweep)
    cell["traffic_file"]["threads"] = [1, 4, 16]
    return cell


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_reference_matches_every_cell_of_a_small_sweep(cell):
    from repro.sim.workloads import run_sweep
    horizon = cell["config_file"]["sweep"]["horizon"] // 10
    cell = small(cell, horizon=horizon)
    rows = run_sweep(runner.sweep_spec(cell, runner.sweep_seeds(2**31 + 3, 0, 2)))
    for row in rows:
        expected = ref.run_cell(lock=row["lock"], n_threads=row["n_threads"],
                                seed=row["seed"], sweep=cell["config_file"]["sweep"])
        assert runner.mismatches(row, expected) == [], (row["lock"], row["n_threads"])


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_the_control_reads_not_correct(cell):
    control = harness.load_module(BENCH / "control.py", "t_control")
    horizon = cell["config_file"]["sweep"]["horizon"] // 10
    cell = small(cell, horizon=horizon)
    for seed in (1, 2, 2**31 + 7):
        sweeps = control.window_rows(cell, runner, ref, seed, sweeps=1)
        checks = runner.compare(cell, sweeps, seed, mutate=control.MUTATION)
        assert any(c["value"] > c["limit"] for c in checks), (seed, checks)
