"""Work-stealing scheduler validation: ``mode="sched"`` must be bit-identical
to ``mode="map"`` on skewed sweeps (only lane placement may change), refill
must handle every queue/lane geometry, and a sched sweep must cost a single
engine compilation.  The TPU's geometry and the longest-first queue are
forced here on the CPU (``jax.default_backend`` patched to ``"tpu"``), since
only a chip resolves them otherwise."""

from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.sim import SweepSpec
from repro.sim.engine import engine_cache_info
from repro.sim import engine
from repro.sim.engine import OUT_KEYS
from repro.sim.workloads import pack_engine_cells, run_sweep


def _skewed_sweep_args():
    """Engine-level sweep with uneven thread counts, horizons, and programs:
    one heavy cell towering over many light ones."""
    cells = [("twa", 6, 150_000), ("ticket", 2, 12_000), ("mcs", 3, 12_000),
             ("ticket", 5, 20_000), ("twa", 2, 8_000), ("anderson", 4, 15_000),
             ("ticket", 3, 0), ("twa", 4, 25_000)]  # one zero-horizon cell
    return pack_engine_cells(cells, ncs_max=100, seeds=5)


def _one_past_the_tpu_lanes():
    """``engine.TPU_LANES + 1`` light cells, the heaviest submitted last: one
    of the lightest waits in the queue for the first lane to free up."""
    locks = ("ticket", "twa", "mcs", "anderson")
    cells = [(locks[i % 4], 2 + i % 3, 1_000 + 250 * (i % 5))
             for i in range(engine.TPU_LANES)]
    cells.append(("twa", 6, 12_000))
    return pack_engine_cells(cells, ncs_max=100, seeds=7)


def _tied_estimates():
    """``engine.TPU_LANES + 4`` cells of equal ``horizon × n_active`` whose
    event counts differ, and a zero-horizon cell among them: the queue keeps
    submission order, the zero-horizon cell last.  That cell's lock starts
    from nonzero memory, which it has to keep."""
    tied = [("ticket", 4, 3_000), ("twa", 2, 6_000), ("mcs", 3, 4_000),
            ("anderson", 6, 2_000)]
    cells = [tied[i % 4] for i in range(engine.TPU_LANES + 3)]
    cells.insert(5, ("anderson", 3, 0))
    return pack_engine_cells(cells, ncs_max=100, seeds=3)


TPU_GEOMETRY_CASES = {
    "fewer_cells_than_tpu_lanes": _skewed_sweep_args,
    "one_cell_past_the_tpu_lanes": _one_past_the_tpu_lanes,
    "tied_estimates": _tied_estimates,
}


def _assert_same(ref: dict, out: dict, ctx) -> None:
    for key in OUT_KEYS:
        assert np.array_equal(ref[key], out[key]), (ctx, key)


def test_sched_matches_map_on_skewed_sweep():
    """Uneven n_active / horizons / programs: every per-cell stat — including
    the zero-horizon cell's untouched init memory — must match map mode."""
    programs, kw = _skewed_sweep_args()
    ref = engine.run_sweep(programs, mode="map", **kw)
    out = engine.run_sweep(programs, mode="sched", lanes=3, chunk=128, **kw)
    _assert_same(ref, out, "skewed")
    # the zero-horizon cell ran no events and kept its initial memory
    assert ref["events"][6] == 0
    assert np.array_equal(out["grant_value"][6], kw["init_mem"][6])


def test_sched_lane_refill_edge_cases():
    """Queue/lane geometry edges: more lanes than cells (B < lanes), many
    refill waves (B >> lanes), and every lane finishing in the same chunk."""
    programs, kw = _skewed_sweep_args()
    ref = engine.run_sweep(programs, mode="map", **kw)
    for lanes, chunk in ((32, 64),       # B < lanes: surplus lanes idle
                         (1, 64),        # B >> lanes: B refill waves
                         (8, 1 << 20)):  # all lanes finish in chunk one
        out = engine.run_sweep(programs, mode="sched",
                               lanes=lanes, chunk=chunk, **kw)
        _assert_same(ref, out, (lanes, chunk))


def test_sched_workloads_plumbing_bit_identity():
    """The SweepSpec path must thread lanes/chunk through to the engine and
    stay bit-identical to map mode."""
    spec = SweepSpec(locks=("ticket", "twa"), threads=(2, 5), seeds=(1, 2),
                     horizon=30_000)
    ref = run_sweep(spec, mode="map")
    out = run_sweep(spec, mode="sched", lanes=2, chunk=100)
    for a, b in zip(ref, out):
        assert np.array_equal(a["acquisitions"], b["acquisitions"])
        assert a["events"] == b["events"]
        assert np.array_equal(a["mem"], b["mem"])
        assert a["throughput"] == b["throughput"]


def test_sched_single_compile_and_geometry_keyed_cache():
    """One sched sweep = one engine compile; re-running with different data
    reuses it; a different lane geometry is a different cache entry."""
    spec = SweepSpec(locks=("ticket", "mcs"), threads=(2, 4), seeds=1,
                     horizon=20_000)
    before = engine_cache_info()
    run_sweep(spec, mode="sched", lanes=2, chunk=64)
    after = engine_cache_info()
    assert after.currsize - before.currsize == 1
    assert after.misses - before.misses == 1
    run_sweep(SweepSpec(locks=("ticket", "mcs"), threads=(2, 4), seeds=7,
                        horizon=20_000), mode="sched", lanes=2, chunk=64)
    again = engine_cache_info()
    assert again.currsize == after.currsize
    assert again.misses == after.misses
    run_sweep(spec, mode="sched", lanes=3, chunk=64)
    keyed = engine_cache_info()
    assert keyed.currsize - again.currsize == 1


@pytest.mark.parametrize("case", sorted(TPU_GEOMETRY_CASES))
def test_tpu_geometry_and_longest_first_queue_match_map(case, monkeypatch):
    """The geometry a TPU resolves (as many lanes as cells, up to
    ``TPU_LANES``, bursts of ``TPU_CHUNK``) and the longest-first queue
    leave every statistic bit-identical to map mode, the zero-horizon
    cell's untouched memory included."""
    programs, kw = TPU_GEOMETRY_CASES[case]()
    ref = engine.run_sweep(programs, mode="map", **kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    out = engine.run_sweep(programs, mode="sched", **kw)
    _assert_same(ref, out, case)
    lanes = min(len(programs), engine.TPU_LANES)
    ps = out["pad_stats"]
    assert ps["lanes"] == lanes
    assert ps["lane_steps"] % (lanes * engine.TPU_CHUNK) == 0
    zero = kw["horizon"] == 0
    assert zero.any() or case == "one_cell_past_the_tpu_lanes"
    assert (out["events"][zero] == 0).all()
    assert np.array_equal(out["grant_value"][zero], kw["init_mem"][zero])


def test_queue_order_is_a_stable_descending_sort_of_the_estimate():
    for case in sorted(TPU_GEOMETRY_CASES):
        programs, kw = TPU_GEOMETRY_CASES[case]()
        est = kw["horizon"].astype(np.int64) * kw["n_active"]
        order = engine.queue_order(len(programs), kw["horizon"],
                                   kw["n_active"])
        assert order.dtype == np.int32
        assert order.tolist() == sorted(range(len(est)),
                                        key=lambda i: -est[i]), case
    # a scalar horizon broadcasts; ties keep submission order
    assert engine.queue_order(5, 1_000, [1, 4, 2, 4, 0]).tolist() == \
        [1, 3, 2, 0, 4]


def test_tpu_geometry_depends_only_on_shapes(monkeypatch):
    """The benchmark's warm-up at horizon 1 compiles the executable the real
    sweep runs: both resolve one ``(lanes, chunk)`` and one compile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = SweepSpec(locks=("ticket", "twa"), threads=(1, 2, 7), seeds=(3, 4),
                     horizon=9_000)
    warm = replace(spec, horizon=1)
    assert engine.sched_geometry("tpu", warm.n_cells()) == \
        engine.sched_geometry("tpu", spec.n_cells()) == \
        (min(spec.n_cells(), engine.TPU_LANES), engine.TPU_CHUNK)
    before = engine_cache_info()
    warm_rows = run_sweep(warm)
    mid = engine_cache_info()
    rows = run_sweep(spec)
    after = engine_cache_info()
    assert warm_rows[0]["mode"] == rows[0]["mode"] == "sched"
    assert warm_rows[0]["pad_stats"]["lanes"] == \
        rows[0]["pad_stats"]["lanes"] == min(spec.n_cells(), engine.TPU_LANES)
    assert (mid.misses - before.misses, after.misses - mid.misses) == (1, 0)
