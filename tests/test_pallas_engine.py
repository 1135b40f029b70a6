"""Pallas fast-path validation: ``mode="pallas"`` must be bit-identical to
``mode="map"`` on skewed sweeps for every burst chunk (overshoot steps are
identity no-events), must refuse to lower natively rather than drop to the
interpreter, ``mode="auto"`` must pick different drivers at different
(backend, sweep-shape) points and never ``"pallas"``, and every sweep must
stamp its resolved mode and padding-waste report into the result."""

import logging

import jax
import numpy as np
import pytest

from repro.sim import SweepSpec, choose_mode
from repro.sim import engine
from repro.sim.engine import engine_cache_info
from repro.sim.engine_pallas import OUT_KEYS
from repro.sim.workloads import pack_engine_cells, run_sweep

ON_CPU = jax.default_backend() == "cpu"


def _skewed_sweep_args():
    """One heavy cell towering over light ones, plus a zero-horizon cell."""
    cells = [("twa", 6, 150_000), ("ticket", 2, 12_000), ("mcs", 3, 12_000),
             ("ticket", 5, 20_000), ("twa", 2, 8_000), ("anderson", 4, 15_000),
             ("ticket", 3, 0), ("twa", 4, 25_000)]
    return pack_engine_cells(cells, ncs_max=100, seeds=5)


def _assert_same(ref: dict, out: dict, ctx) -> None:
    for key in OUT_KEYS:
        assert np.array_equal(ref[key], out[key]), (ctx, key)


def test_pallas_matches_map_on_skewed_sweep():
    """Uneven n_active / horizons / programs: every per-cell stat — including
    the zero-horizon cell's untouched init memory — must match map mode."""
    programs, kw = _skewed_sweep_args()
    ref = engine.run_sweep(programs, mode="map", **kw)
    out = engine.run_sweep(programs, mode="pallas", **kw)
    _assert_same(ref, out, "skewed")
    assert out["mode"] == "pallas"
    # the zero-horizon cell ran no events and kept its initial memory
    assert ref["events"][6] == 0
    assert np.array_equal(out["grant_value"][6], kw["init_mem"][6])


def test_pallas_chunk_edge_cases():
    """Burst-chunk edges: chunk=1 (termination check after every event, no
    overshoot) and a chunk far beyond any cell's event count (every cell
    finishes inside burst one, maximum overshoot)."""
    programs, kw = _skewed_sweep_args()
    ref = engine.run_sweep(programs, mode="map", **kw)
    for chunk in (1, 1 << 20):
        out = engine.run_sweep(programs, mode="pallas", chunk=chunk, **kw)
        _assert_same(ref, out, chunk)


def test_pallas_interpret_flag_is_pallas_only():
    """Explicit interpret=True must work for pallas and be rejected
    (loudly, not ignored) for every other driver."""
    programs, kw = _skewed_sweep_args()
    ref = engine.run_sweep(programs, mode="map", **kw)
    out = engine.run_sweep(programs, mode="pallas", interpret=True, **kw)
    _assert_same(ref, out, "interpret=True")
    with pytest.raises(AssertionError):
        engine.run_sweep(programs, mode="map", interpret=True, **kw)


def test_pallas_workloads_plumbing_bit_identity():
    """The SweepSpec path must thread chunk/interpret through to the engine
    and stay bit-identical to map mode, stamping the resolved driver."""
    spec = SweepSpec(locks=("ticket", "twa"), threads=(2, 5), seeds=(1, 2),
                     horizon=30_000)
    ref = run_sweep(spec, mode="map")
    out = run_sweep(spec, mode="pallas", chunk=64)
    for a, b in zip(ref, out):
        assert np.array_equal(a["acquisitions"], b["acquisitions"])
        assert a["events"] == b["events"]
        assert np.array_equal(a["mem"], b["mem"])
        assert a["throughput"] == b["throughput"]
        assert a["mode"] == "map" and b["mode"] == "pallas"


def test_pallas_single_compile_and_chunk_keyed_cache():
    """One pallas sweep = one engine compile; re-running with different data
    reuses it; a different burst chunk is a different cache entry."""
    spec = SweepSpec(locks=("ticket", "mcs"), threads=(2, 4), seeds=1,
                     horizon=20_000)
    before = engine_cache_info()
    run_sweep(spec, mode="pallas", chunk=64)
    after = engine_cache_info()
    assert after.currsize - before.currsize == 1
    assert after.misses - before.misses == 1
    run_sweep(SweepSpec(locks=("ticket", "mcs"), threads=(2, 4), seeds=7,
                        horizon=20_000), mode="pallas", chunk=64)
    again = engine_cache_info()
    assert again.currsize == after.currsize
    assert again.misses == after.misses
    run_sweep(spec, mode="pallas", chunk=32)
    keyed = engine_cache_info()
    assert keyed.currsize - again.currsize == 1


def test_choose_mode_selects_distinct_drivers():
    """The auto policy must pick different drivers at distinct
    (backend, sweep-shape) points — the whole point of mode="auto" — and
    never the pallas driver, which Mosaic refuses on a TPU."""
    uniform = dict(n_cells=4, n_threads=8, horizon=10_000)
    skew_h = np.asarray([600_000] + [10_000] * 11)
    skewed = dict(n_cells=12, n_threads=8, horizon=skew_h)
    assert choose_mode("cpu", **uniform) == "map"
    assert choose_mode("cpu", **skewed) == "sched"
    assert choose_mode("tpu", **uniform) == "vmap"
    assert choose_mode("tpu", **skewed) == "sched"
    # the fig3 grid: one horizon, thread counts 1..64 -> skewed by n_active
    fig3_active = np.repeat([1, 2, 4, 8, 16, 32, 64], 39)
    assert choose_mode("tpu", n_cells=273, n_threads=64, horizon=1_500_000,
                       n_active=fig3_active) == "sched"
    # the skew gate needs enough cells for stealing to pay off
    few = dict(n_cells=2, n_threads=8,
               horizon=np.asarray([600_000, 10_000]))
    assert choose_mode("cpu", **few) == "map"
    assert choose_mode("tpu", **few) == "vmap"


def test_pallas_refuses_native_lowering():
    """A native pallas sweep must fail loudly, naming Mosaic's refusal —
    never fall back to the interpreter behind the caller's back."""
    programs, kw = _skewed_sweep_args()
    with pytest.raises(NotImplementedError, match="Mosaic"):
        engine.run_sweep(programs, mode="pallas", interpret=False, **kw)


def test_auto_mode_on_tpu_backend_picks_xla_driver(monkeypatch):
    """Where the backend reports a TPU, auto resolves to an XLA driver."""
    monkeypatch.setattr(engine.jax, "default_backend", lambda: "tpu")
    cells = [("ticket", 2, 10_000), ("twa", 2, 10_000)]
    programs, kw = pack_engine_cells(cells, ncs_max=100, seeds=3)
    out = engine.run_sweep(programs, mode="auto", **kw)
    assert out["mode"] == "vmap"
    monkeypatch.undo()
    _assert_same(engine.run_sweep(programs, mode="map", **kw), out,
                 "auto-tpu")


@pytest.mark.skipif(not ON_CPU, reason="asserts the CPU auto policy")
def test_auto_mode_resolves_by_sweep_shape(caplog):
    """On the CPU backend, auto must resolve to different drivers for a
    uniform vs a skewed sweep, log the choice, and stamp it in the result."""
    programs, kw = _skewed_sweep_args()
    with caplog.at_level(logging.INFO, logger="repro.sim.engine"):
        out = engine.run_sweep(programs, mode="auto", **kw)
    assert out["mode"] == "sched"
    assert any("mode='auto' -> 'sched'" in r.getMessage()
               for r in caplog.records)
    cells = [("ticket", 2, 10_000), ("twa", 2, 10_000)]
    programs2, kw2 = pack_engine_cells(cells, ncs_max=100, seeds=3)
    out2 = engine.run_sweep(programs2, mode="auto", **kw2)
    assert out2["mode"] == "map"
    ref2 = engine.run_sweep(programs2, mode="map", **kw2)
    _assert_same(ref2, out2, "auto-uniform")


def test_pad_stats_waste_report():
    """Every run_sweep result carries the padding-waste report; the
    fractions must reflect the actual thread/program padding."""
    cells = [("ticket", 2, 10_000), ("twa", 6, 10_000)]
    programs, kw = pack_engine_cells(cells, ncs_max=100, seeds=3)
    out = engine.run_sweep(programs, mode="map", **kw)
    ps = out["pad_stats"]
    assert ps["sum_events"] == int(out["events"].sum())
    assert ps["max_events"] == int(out["events"].max())
    n_threads = kw["init_pc"].shape[1]
    expect_threads = np.asarray(kw["n_active"]).sum() / (2 * n_threads)
    assert ps["live_thread_frac"] == pytest.approx(expect_threads)
    assert 0 < ps["live_prog_frac"] < 1  # programs are padded to PROG_LEN
    assert 0 < ps["live_mem_frac"] <= 1
