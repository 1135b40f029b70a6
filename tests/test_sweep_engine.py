"""Batched-sweep engine validation: run_sweep must be bit-equivalent to
sequential run_sim, shape padding must be invisible, an entire sweep must
cost a single engine compilation, ``mode="auto"`` must pick different
drivers at different (backend, sweep-shape) points, an unknown driver or a
geometry given to a driver without one must be refused, and every sweep
must stamp its resolved mode and padding-waste report into the result."""

import logging

import jax
import numpy as np
import pytest

from repro.sim import (SIM_LOCKS, SweepSpec, choose_mode, engine, pad_program,
                       pad_threads, run_contention, run_sweep)
from repro.sim.engine import OUT_KEYS, engine_cache_info, run_sim
from repro.sim.faults import FaultSchedule
from repro.sim.programs import (INIT_MEM_GEN, Layout, build_mutexbench,
                                init_state)
from repro.sim.workloads import pack_engine_cells

H = 120_000


def _run_sim_cell(lock, n_threads, *, seed, horizon=H, n_locks=1,
                  private_arrays=False, cs_work=4, ncs_max=200):
    layout = Layout(n_threads=n_threads, n_locks=n_locks,
                    private_arrays=private_arrays)
    prog = build_mutexbench(lock, layout, cs_work=cs_work, ncs_max=ncs_max)
    pc, regs = init_state(layout)
    gen_mem = INIT_MEM_GEN.get(lock)
    return run_sim(prog, n_threads=n_threads, mem_words=layout.mem_words,
                   n_locks=n_locks, init_pc=pc, init_regs=regs,
                   wa_base=layout.wa_base, wa_size=layout.wa_size,
                   horizon=horizon, seed=seed,
                   init_mem=gen_mem(layout) if gen_mem else None)


def test_sweep_matches_sequential_run_sim():
    """Every cell of a padded, vmapped sweep must match an unpadded
    sequential run_sim bit for bit — stats, per-thread counts, and memory."""
    spec = SweepSpec(locks=("ticket", "twa", "anderson"), threads=(2, 5),
                     seeds=(1, 2), horizon=H)
    for r in run_sweep(spec):
        ref = _run_sim_cell(r["lock"], r["n_threads"], seed=r["seed"])
        assert np.array_equal(r["acquisitions"], ref["acquisitions"]), \
            (r["lock"], r["n_threads"], r["seed"])
        assert r["events"] == ref["events"]
        assert r["handover_sum"] == ref["handover_sum"]
        assert np.array_equal(r["mem"], ref["mem"])
        assert r["throughput"] == ref["throughput"]


def test_thread_padding_is_invisible():
    """Masked inactive threads must not perturb the active ones."""
    layout = Layout(n_threads=4, n_locks=1)
    prog = build_mutexbench("twa", layout)
    pc, regs = init_state(layout)
    ref = run_sim(prog, n_threads=4, mem_words=layout.mem_words, n_locks=1,
                  init_pc=pc, init_regs=regs, wa_base=layout.wa_base,
                  wa_size=layout.wa_size, horizon=H, seed=3)
    pc9, regs9 = pad_threads(pc, regs, 9)
    padded = run_sim(prog, n_threads=9, mem_words=layout.mem_words, n_locks=1,
                     init_pc=pc9, init_regs=regs9, wa_base=layout.wa_base,
                     wa_size=layout.wa_size, horizon=H, seed=3, n_active=4)
    assert np.array_equal(ref["acquisitions"], padded["acquisitions"][:4])
    assert (padded["acquisitions"][4:] == 0).all()
    assert ref["events"] == padded["events"]


def test_sweep_single_compile_across_thread_counts():
    """A sweep over several thread counts (and locks and seeds) must hit
    exactly one _build_engine cache entry; re-running with different data
    (new seeds) must add none."""
    before = engine_cache_info()
    spec = SweepSpec(locks=("ticket", "mcs"), threads=(3, 6, 7), seeds=1,
                     horizon=60_000)
    run_sweep(spec)
    after = engine_cache_info()
    assert after.currsize - before.currsize == 1
    assert after.misses - before.misses == 1
    run_sweep(SweepSpec(locks=("ticket", "mcs"), threads=(3, 6, 7), seeds=9,
                        horizon=60_000))
    again = engine_cache_info()
    assert again.currsize == after.currsize
    assert again.misses == after.misses


# MutexBench's 13 locks (Figure 3): every single-lock algorithm that holds
# 64 threads; twa-timo's 32-slot abandonment ring cannot.
MUTEXBENCH_LOCKS = tuple(lk for lk in SIM_LOCKS if lk != "twa-timo")
# the drivers compared against mode="map", as (mode, lanes)
DRIVERS = (("vmap", None), ("sched", 1), ("sched", 3), ("sched", 4))
# result keys that describe the driver, not the cell
DRIVER_KEYS = ("mode", "pad_stats")


# The sweeps compared, as (locks, threads, horizon, faults): every
# MutexBench lock at 3 and 33 threads (two bitset words), without and with
# faults (preemptions, a spurious wake and an abort per cell); and ticket
# and twa at 2 and 4 threads over a long horizon.
GEOMETRIES = {
    "fault-free": (MUTEXBENCH_LOCKS, (3, 33), 8_000, False),
    "faults": (MUTEXBENCH_LOCKS, (3, 33), 8_000, True),
    "long": (("ticket", "twa"), (2, 4), 60_000, False),
}


@pytest.fixture(scope="module")
def mode_sweeps(request) -> dict:
    """One sweep of a geometry, latency histograms on, run by each
    driver."""
    locks, threads, horizon, faults = GEOMETRIES[request.param]
    fault_axes = (dict(preempt_faults=2, spurious_faults=1, abort_faults=1,
                       preempt_cost=300, fault_evt_span=400)
                  if faults else {})
    spec = SweepSpec(locks=locks, threads=threads, seeds=1, horizon=horizon,
                     collect_latency=True, **fault_axes)
    runs = {("map", None): run_sweep(spec, mode="map")}
    for mode, lanes in DRIVERS:
        runs[mode, lanes] = run_sweep(spec, mode=mode, lanes=lanes)
    return runs


def _same(a, b) -> bool:
    if isinstance(a, FaultSchedule):
        return a.to_lists() == b.to_lists()
    if isinstance(a, float) and np.isnan(a):
        return np.isnan(b)
    return np.array_equal(a, b)


@pytest.mark.parametrize(
    "mode_sweeps,lock",
    [(name, lock) for name, (locks, *_) in GEOMETRIES.items()
     for lock in locks],
    indirect=["mode_sweeps"])
def test_sweep_modes_bitwise_equal(lock, mode_sweeps):
    """The lane-parallel (vmap) and work-stealing (sched, 1, 3 and 4 lanes)
    drivers must reproduce the sequential (map) driver's every result key,
    cell by cell."""
    ref = mode_sweeps["map", None]
    rows = [i for i, r in enumerate(ref) if r["lock"] == lock]
    assert len(rows) == 2
    for driver in DRIVERS:
        for i in rows:
            got, want = mode_sweeps[driver][i], ref[i]
            assert got.keys() == want.keys()
            for key in want.keys() - set(DRIVER_KEYS):
                assert _same(got[key], want[key]), \
                    (driver, lock, want["n_threads"], key)


def test_sweep_cells_cartesian_order():
    spec = SweepSpec(locks=("a", "b"), threads=(1, 2), seeds=(7,),
                     cs_work=(4, 8))
    cells = spec.cells()
    assert len(cells) == 8
    assert [c.lock for c in cells[:4]] == ["a"] * 4
    assert [(c.n_threads, c.cs_work) for c in cells[:4]] == \
        [(1, 4), (1, 8), (2, 4), (2, 8)]


def test_pad_program_idempotent_and_bounded():
    layout = Layout(n_threads=2, n_locks=1)
    prog = build_mutexbench("ticket", layout)
    padded = pad_program(prog)
    assert padded.shape == (256, 5)
    assert np.array_equal(pad_program(padded), padded)
    with pytest.raises(AssertionError):
        pad_program(padded, 128)


def test_anderson_requires_private_arrays_for_multilock():
    layout = Layout(n_threads=4, n_locks=2)
    with pytest.raises(ValueError):
        build_mutexbench("anderson", layout)
    # per-lock (private) arrays are safe: both locks stay FIFO-fair
    res = run_contention("anderson", 8, n_locks=2, private_arrays=True,
                         horizon=H)
    acq = res["acquisitions"]
    assert acq.min() > 0
    assert acq.min() >= 0.8 * acq.max(), acq


# ---------------------------------------------------------------------------
# Driver choice, refusals and the padding-waste report
# ---------------------------------------------------------------------------

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


def test_choose_mode_selects_distinct_drivers():
    """The auto policy must pick different drivers at distinct
    (backend, sweep-shape) points — the whole point of mode="auto"."""
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


def _sweep_at_engine(mode, **geometry):
    programs, kw = pack_engine_cells([("ticket", 2, 1_000)], ncs_max=100,
                                     seeds=1)
    return engine.run_sweep(programs, mode=mode, **geometry, **kw)


def _sweep_at_workloads(mode, **geometry):
    spec = SweepSpec(locks=("ticket",), threads=(2,), seeds=1, horizon=1_000)
    return run_sweep(spec, mode=mode, **geometry)


@pytest.mark.parametrize("entry", [_sweep_at_engine, _sweep_at_workloads],
                         ids=["engine", "workloads"])
def test_unknown_driver_is_refused(entry):
    """A driver name outside ``engine.DRIVERS`` is the caller's error: a
    ValueError that names the drivers there are."""
    with pytest.raises(ValueError, match="'map', 'vmap', 'sched'"):
        entry("pallas")


@pytest.mark.parametrize("mode,knob", [(mode, knob)
                                       for mode in ("map", "vmap")
                                       for knob in ("lanes", "chunk")])
def test_geometry_is_sched_only(mode, knob):
    """``lanes`` and ``chunk`` shape the sched driver alone; given to
    another driver they are refused, not ignored."""
    with pytest.raises(ValueError, match="only apply to mode='sched'"):
        _sweep_at_engine(mode, **{knob: 4})
