"""The harness finds everything by name, refuses without a TPU, and prints
the contract's last line."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness
from conftest import BENCH, REPO

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def test_every_cell_and_metric_resolves_to_its_files():
    for w in BENCHMARK["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"]), w["name"]
        assert (BENCH / "runners" / f"{cell['runner']}.py").is_file()
    for c in BENCHMARK["configs"]:
        config = harness.load_json(REPO / c["file"])
        assert config["name"] == c["name"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "reference" / f"{config['reference']}.py").is_file()
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     f"t_{m['name']}")
        assert callable(reader.read)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    e2e = {m["name"] for m in harness.cell_metrics(BENCHMARK, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(BENCHMARK, True)
    assert not any("workloads" in m for m in BENCHMARK["end_to_end"]
                   + BENCHMARK["per_layer"])


def test_no_file_of_the_benchmark_imports_the_figure_scripts():
    imports = re.compile(r"^\s*(import|from)\s+benchmarks\b", re.MULTILINE)
    for path in BENCH.rglob("*.py"):
        assert not imports.search(path.read_text()), path


def test_a_cell_and_a_metric_added_as_files_are_picked_up(tmp_path):
    copy = tmp_path / "bench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("out", "__pycache__"))
    (copy / "traffic" / "t8.json").write_text(
        json.dumps({"threads": [8], "seeds_per_sweep": 2}))
    (copy / "workloads" / "mutexbench.t8.json").write_text(json.dumps(
        {"config": "mutexbench", "traffic": "t8", "runner": "lockvm_sweep",
         "chips": 1}))
    (copy / "metrics" / "sweeps_per_s.py").write_text(
        "def read(run):\n    return run['sweeps'] / run['window_s']\n")
    copied = harness.load_module(copy / "harness.py", "copied_harness")
    assert copied.BENCH == copy
    cell = copied.load_cell("mutexbench.t8")
    assert cell["traffic_file"]["threads"] == [8]
    benchmark = {"end_to_end": [{"name": "sweeps_per_s", "unit": "1/s"}]}
    metrics = copied.cell_metrics(benchmark, False)
    assert copied.read_metrics(metrics, {"sweeps": 3, "window_s": 2.0}) == {
        "sweeps_per_s": {"value": 1.5, "unit": "1/s"}}


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    metrics = harness.cell_metrics(BENCHMARK, True)
    assert harness.read_metrics(metrics, {"trace": None}) == {}


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_the_runner_exits_nonzero_without_a_tpu():
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "mutexbench.t64",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "no TPU" in done.stderr


def test_the_runner_exits_nonzero_beside_only_its_own_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = dict(_env(), PYTHONPATH="")
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mutexbench.t64",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with small cells, for runs on the CPU.

    ``tiny`` and ``tinylt`` span 1 to 8 threads, so ``run_sweep`` picks
    ``sched``; ``tiny8`` runs 8 threads only and gets ``vmap``; ``tinypre``
    adds preemption windows, which a configuration may state as data.
    """
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    small = {"locks": ["ticket", "twa", "mcs", "clh"], "horizon": 3000,
             "max_events": 20000}
    for name, base, traffic, sweep in (
            ("tiny", "mutexbench", "tiny", small),
            ("tiny8", "mutexbench", "tiny8", small),
            ("tinylt", "locktorture", "tiny", {"horizon": 6000}),
            ("tinypre", "locktorture", "tiny",
             {"horizon": 6000, "max_events": 12000, "preempt_faults": 4,
              "preempt_cost": 2048, "fault_evt_span": 750})):
        config = json.loads((BENCH / "configs" / f"{base}.json").read_text())
        config["sweep"].update(sweep)
        config["name"] = name
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))
        (root / "bench" / "workloads" / f"{name}.json").write_text(json.dumps(
            {"config": name, "traffic": traffic, "runner": "lockvm_sweep",
             "chips": 1}))
    (root / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps({"threads": [1, 2, 8], "seeds_per_sweep": 2}))
    (root / "bench" / "traffic" / "tiny8.json").write_text(
        json.dumps({"threads": [8], "seeds_per_sweep": 2}))
    shutil.copy(REPO / "BENCHMARK.json", root)
    return root


def drive(root: Path, workload: str, *, fault: str | None = None,
          trace: int = 0, seed: int = 2**31 + 11) -> tuple[dict, str]:
    cmd = [sys.executable, str(BENCH / "tests" / "drive.py"), str(root / "bench")]
    if fault:
        cmd += ["--fault", fault]
    cmd += ["--", "--workload", workload, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]
    done = subprocess.run(cmd, cwd=root, env=_env(), capture_output=True,
                          text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    last = done.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") for s in last), last
    window = next(json.loads(s.split(" ", 1)[1])
                  for s in done.stderr.splitlines() if s.startswith("window "))
    return line, window["mode"]


CELLS = {"tiny": "sched", "tiny8": "vmap", "tinylt": "sched", "tinypre": "sched"}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_sound_run_is_correct_with_exactly_the_contracts_keys(tiny, workload):
    line, mode = drive(tiny, workload)
    assert mode == CELLS[workload]
    assert set(line) == CONTRACT_KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"sim_events_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert all(c["value"] <= c["limit"] for c in line["checks"])


@pytest.mark.parametrize("fault", ["frozen_step", "half_batch", "altered_answer"])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_broken_timed_path_reads_not_correct(tiny, workload, fault):
    line, _ = drive(tiny, workload, fault=fault)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"])


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**32 + 17])
def test_a_fault_in_one_vmap_lane_reads_not_correct(tiny, seed):
    line, mode = drive(tiny, "tiny8", fault="one_lane", seed=seed)
    assert mode == "vmap"
    assert line["correct"] is False
    assert line["checks"][0]["of"] >= 8  # a whole sweep, the longest cell beside it
