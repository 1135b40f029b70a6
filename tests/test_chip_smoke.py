"""chip_smoke.py's phases at a tiny size on the host backend, its refusal
to run without a TPU, and the fig3 grid it sweeps on the chip."""

import importlib.util
import os
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)  # puts src/ and the root on sys.path

from benchmarks import fig3_mutexbench  # noqa: E402
from repro.sim.workloads import run_sweep  # noqa: E402

ON_TPU = jax.default_backend() == "tpu"


@pytest.mark.skipif(ON_TPU, reason="checks the no-TPU refusal")
def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_fig3_tiny():
    out = chip_smoke.phase_fig3(locks=("ticket", "twa"), threads=(1, 4),
                                runs=1, horizon=2_000)
    assert out["cells"] == 4 and out["sum_events"] > 0
    assert out["driver"] in chip_smoke.CHIP_DRIVERS


def test_phase_drivers_tiny():
    out = chip_smoke.phase_drivers([("twa", 4, 5_000), ("ticket", 2, 3_000),
                                    ("mcs", 3, 3_000)])
    assert out["cells"] == 3 and out["sum_events"] > 0


def test_phase_corpus_subset():
    """One clean differential pin, one fault pin, one broken-lock pin."""
    names = ("diff_lost_wake", "fault_spurious_twa",
             "inv_exclusion_sem_double_release")
    out = chip_smoke.phase_corpus(
        [os.path.join(ROOT, "tests", "corpus", f"{n}.npz") for n in names])
    assert out == {"entries": 3, "flagged": 1}


def test_phase_serve_tiny(monkeypatch, tmp_path):
    # a set cache dir is left alone, so this test turns no cache on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = chip_smoke.phase_serve(
        ("--arch", "granite-moe-1b-a400m", "--reduced", "--requests", "3",
         "--lanes", "2", "--max-ctx", "64", "--max-new", "4"))
    assert out == {"requests": 3, "tokens": 12}


def test_fig3_grid_builds_every_cell():
    """Every fig3 cell (13 locks x 7 thread counts x 3 seeds) builds its
    program and runs at fig3's padded shapes; twa-timo, whose 32-slot ring
    cannot hold 64 threads, is not in the grid."""
    spec = fig3_mutexbench.spec(horizon=1)
    results = run_sweep(spec, mode="map")
    coords = {(r["lock"], r["n_threads"], r["seed"]) for r in results}
    assert len(results) == len(coords) == 273
    assert "twa-timo" not in {r["lock"] for r in results}
    assert max(r["n_threads"] for r in results) == 64
