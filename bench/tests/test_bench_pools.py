"""The pool cell: its plain reference agrees with the lockVM over pools of
locks, both of its controls are caught by the comparison, a run of the cell
is correct where it should be and not where its timed path is broken, and
the reader of the index form's step cost finds its sweeps in a trace."""

import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

import harness
import spans
from conftest import BENCH, REPO

ref = harness.load_module(BENCH / "reference" / "lockvm_pool.py",
                          "t_ref_lockvm_pool")
runner = harness.load_module(BENCH / "runners" / "lockvm_pool.py",
                             "t_runner_lockvm_pool")
CELL = harness.load_cell("interlock.pools")
POOLS = (1, 2, 4)


def small(pools=POOLS, threads=(2, 5, 8), horizon=3_000) -> dict:
    cell = json.loads(json.dumps(CELL))
    cell["config_file"]["sweep"]["horizon"] = horizon
    cell["traffic_file"].update(pools=list(pools), threads=list(threads))
    return cell


@pytest.mark.parametrize("private", [False, True], ids=["shared", "private"])
@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("lock", sorted(ref.LOCKS))
def test_pool_programs_and_memory_match_the_lockvm_builder(lock, pool,
                                                            private):
    from repro.sim.programs import INIT_MEM_GEN, Layout, build_mutexbench
    theirs_layout = Layout(n_threads=8, n_locks=pool, private_arrays=private)
    mine_layout = ref.Layout(8, 4096, n_locks=pool, private_arrays=private)
    kw = dict(cs_work=50, ncs_max=100, collect_latency=False)
    if lock == "anderson" and pool > 1 and not private:
        for build, layout in ((build_mutexbench, theirs_layout),
                              (ref.build_mutexbench, mine_layout)):
            with pytest.raises(ValueError):
                build(lock, layout, **kw)
        return
    np.testing.assert_array_equal(
        np.asarray(ref.build_mutexbench(lock, mine_layout, **kw), np.int32),
        build_mutexbench(lock, theirs_layout, **kw))
    gen = INIT_MEM_GEN.get(lock)
    np.testing.assert_array_equal(
        ref.init_mem(lock, mine_layout),
        gen(theirs_layout) if gen else np.zeros(theirs_layout.mem_words))


def _rows_match(cell: dict, rows: list[dict]) -> None:
    params = cell["config_file"]["sweep"]
    for row in rows:
        expected = runner.reference(ref, row, params)
        assert runner.single.mismatches(row, expected) == [], (
            row["lock"], row["n_locks"], row["private_arrays"],
            row["n_threads"], row["seed"])


@pytest.mark.parametrize("pool", POOLS)
def test_the_reference_matches_every_cell_of_a_small_pool_sweep(pool):
    from repro.sim.workloads import run_sweep
    cell = small()
    seeds = runner.pool_seeds(2**31 + 3, 0, pool, 2)
    rows = run_sweep(runner.pool_spec(cell, pool, seeds))
    assert {bool(r["private_arrays"]) for r in rows} == {False, True}
    assert {int(r["n_locks"]) for r in rows} == {pool}
    _rows_match(cell, rows)


def test_the_reference_matches_every_lock_over_a_pool():
    """Every lock the reference writes out, over 4 locks with private
    arrays, and every lock but anderson with one shared array."""
    from repro.sim.workloads import run_sweep
    cell = small(threads=(3,), horizon=4_000)
    locks = tuple(sorted(ref.LOCKS))
    for arm, names in ((True, locks),
                       (False, tuple(n for n in locks if n != "anderson"))):
        cell["config_file"]["sweep"].update(locks=list(names),
                                            private_arrays=[arm])
        _rows_match(cell, run_sweep(runner.pool_spec(cell, 4, (11,))))


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_both_controls_read_not_correct_on_a_small_cell(seed):
    cell = small(threads=(8,))
    sweeps = runner.control_sweeps(cell, seed)
    for mutation in runner.MUTATIONS:
        checks = runner.compare(cell, sweeps, seed, mutate=(mutation,))
        assert any(c["value"] > c["limit"] for c in checks), (mutation, checks)


def test_both_controls_read_not_correct_at_the_cells_own_size():
    for seed in (3, 2**31 + 5, 2**32 + 17):
        sweeps = runner.control_sweeps(CELL, seed)
        assert {s["pool"] for s in sweeps} == {1, 8, 64}
        assert {r["mode"] for s in sweeps for r in s["rows"]} == {"vmap"}
        for mutation in runner.MUTATIONS:
            checks = runner.compare(CELL, sweeps, seed, mutate=(mutation,))
            assert any(c["value"] > c["limit"] for c in checks), (
                seed, mutation, checks)


def test_the_sample_reaches_every_pool_arm_and_lane():
    sweeps = [{"pool": pool, "rows": [
        {"events": 10 * k + j + (100 if (k, j) == (1, 4) else 0)}
        for j in range(6)]} for k in range(2) for pool in (1, 8, 64)]
    picks = runner.sample(sweeps, seed=5)
    assert len(picks) in range(18, 22)
    for pool in (1, 8, 64):
        mine = [(i, j) for i, j in picks if sweeps[i]["pool"] == pool]
        longest = max(((i, j) for i, s in enumerate(sweeps)
                       if s["pool"] == pool for j in range(6)),
                      key=lambda p: sweeps[p[0]]["rows"][p[1]]["events"])
        assert mine[0] == longest
        assert {j for _, j in mine} == set(range(6))


def _env():
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark whose pool cell runs 8 threads over pools
    of 1, 2 and 4 locks, at a short horizon."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    cell = small(threads=(8,))
    (root / "bench" / "configs" / "interlock.json").write_text(
        json.dumps(cell["config_file"]))
    (root / "bench" / "traffic" / "pools.json").write_text(
        json.dumps(cell["traffic_file"]))
    shutil.copy(REPO / "BENCHMARK.json", root)
    return root


def drive(root, fault=None, seed=2**31 + 11) -> tuple[dict, dict, str]:
    cmd = [sys.executable, str(BENCH / "tests" / "drive.py"),
           str(root / "bench")]
    if fault:
        cmd += ["--fault", fault]
    cmd += ["--", "--workload", "interlock.pools", "--seed", str(seed),
            "--seconds", "1", "--trace", "0"]
    done = subprocess.run(cmd, cwd=root, env=_env(), capture_output=True,
                          text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    window = next(json.loads(s.split(" ", 1)[1])
                  for s in done.stderr.splitlines() if s.startswith("window "))
    return line, window, done.stderr


def test_a_sound_pool_run_is_correct(tiny):
    line, window, stderr = drive(tiny)
    assert line["correct"] is True and line["failed"] == 0
    assert window["mode"] == "vmap" and window["compiles_in_window"] == 0
    assert window["sweeps"] % 3 == 0               # whole passes only
    assert line["checks"][0]["of"] >= 18           # every pool's sweep
    ratios = next(json.loads(s.split(" ", 1)[1])
                  for s in stderr.splitlines() if s.startswith("figure2 "))
    assert set(ratios) == {"1", "2", "4"}


@pytest.mark.parametrize("fault", ["altered_answer", "one_lane"])
def test_a_broken_pool_run_reads_not_correct(tiny, fault):
    line, _, _ = drive(tiny, fault=fault)
    assert line["correct"] is False


# Two sweeps in their bench.run_sweep spans: the first took the index form
# (one execution from 30 to 80, 2 048 lane-steps), the second the mask
# form; a third comes from a program whose dispatch names no form.
L, D = spans.trace.LAUNCH, spans.trace.DONE
HOST = [(0, 100, "bench.run_sweep", {}),
        (25, 35, "lockvm.dispatch", {"mem_form": "index", "n_locks": 8}),
        (30, 31, L, {}), (80, 81, D, {}),
        (90, 95, "lockvm.assemble", {"lanes": 6, "lane_steps": 2048}),
        (150, 300, "bench.run_sweep", {}),
        (155, 160, "lockvm.dispatch", {"mem_form": "mask", "n_locks": 1}),
        (170, 171, L, {}), (280, 281, D, {}),
        (290, 295, "lockvm.assemble", {"lanes": 6, "lane_steps": 600}),
        (400, 500, "bench.run_sweep", {}),
        (405, 410, "lockvm.dispatch", {"n_locks": 1}),
        (420, 421, L, {}), (480, 481, D, {}),
        (490, 495, "lockvm.assemble", {"lanes": 6, "lane_steps": 60})]

index_reader = harness.load_module(BENCH / "metrics"
                                   / "index_ns_per_lane_step.py",
                                   "t_index_ns_per_lane_step")


def test_the_reader_keeps_the_index_form_sweeps_only():
    assert index_reader.index_sweeps(HOST) == [(50, 2048)]
    mask_only = [h for h in HOST if h[0] >= 150]
    assert index_reader.index_sweeps(mask_only) == []
    assert index_reader.read({"trace": None}) is None


def _read_recording(monkeypatch, tmp_path, name: str):
    (tmp_path / "profile").mkdir()
    shutil.copy(BENCH / "tests" / "data" / name, tmp_path / "profile")
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    return index_reader.read({"trace": {"recorded": name}})


def test_a_recorded_mask_only_trace_gives_nothing(monkeypatch, tmp_path):
    """The recording of ``test_bench_spans.py``: three one-lock sweeps,
    made before the dispatch span named its memory form."""
    assert _read_recording(monkeypatch, tmp_path,
                           "tpu_v5e_tiny_sweeps_with_spans.xplane.pb") is None


POOL_RECORDING = "tpu_v5e_pool_sweeps.xplane.pb"


def test_a_recorded_pool_trace_reads_the_index_sweep(monkeypatch, tmp_path):
    """Two traced sweeps on one TPU v5e, twa at 4 threads, shared and
    private arrays, 2 seeds, horizon 400, ``vmap``: a pool of 1 lock (4 544
    words, by mask) and a pool of 8 (35 456 words, by index)."""
    got = _read_recording(monkeypatch, tmp_path, POOL_RECORDING)
    host = spans.extract(spans.trace.find_xplane(tmp_path))
    forms = [str(a.get("mem_form"))
             for _, _, n, a in sorted(host, key=lambda h: h[0])
             if n == index_reader.DISPATCH]
    assert forms == ["mask", "index"]
    ((ns, steps),) = index_reader.index_sweeps(host)
    assert got == pytest.approx(ns / steps)
    assert 0 < got < 1e6
