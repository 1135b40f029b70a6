"""Engine-mode benchmark — map vs vmap vs sched on a skewed sweep, plus
the sched geometry frontier, as host wall time on the backend it runs on.

The batched engine offers three bit-identical sweep drivers; this suite
reports what separates them.  The sweep is skewed on purpose: a few heavy
cells (many threads, long horizon) next to many light ones, so
lane-parallel ``vmap`` pays ``max(events) × B`` lane-steps (idle lanes
still execute the self-guarding no-event step) while ``map`` and the
work-stealing ``sched`` driver pay ~``sum(events)``.

Rows: ``bench_engine/<mode>/wall_ms`` (median of ``repeats`` timed runs,
compile excluded via a warmup call), ``bench_engine/sum_events`` /
``max_events`` (the sweep's skew), padding-waste fractions from the sweep's
``pad_stats`` report, the sweep-wide acquire-latency percentiles
(``bench_engine/lat_p50``/``lat_p99``/``lat_p999`` — the cells run with
``collect_latency=True`` and ``lat_hist`` joins the drivers' bit-identity
assert), ``bench_engine/speedup/<a>_over_<b>`` ratios, and the sched
geometry frontier — one ``bench_engine/frontier/sched/...`` row per
``lanes×chunk`` point.  The same numbers land in ``BENCH_engine.json``
(every mode row and frontier row carries the ``backend`` column).

Every time here is the host's wall clock around ``run_sweep``, host work
included, labelled with the backend it ran on; none is a device metric.
The chip benchmark is ``bench/run.py``.  This suite asserts only that the
drivers agree bit for bit.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax

from repro.sim import engine
from repro.sim.workloads import hist_percentile, pack_engine_cells

from .common import emit

# (lock, n_threads, horizon): two heavy cells amid many light ones
SKEWED_CELLS = (
    [("twa", 32, 600_000), ("ticket", 32, 600_000)]
    + [(lk, t, 40_000)
       for lk in ("ticket", "twa", "mcs") for t in (2, 4, 8)] * 2
)
SMOKE_CELLS = (
    [("twa", 16, 300_000)]
    + [(lk, t, 25_000) for lk in ("ticket", "twa") for t in (2, 4, 8)]
    + [("mcs", 4, 25_000)] * 3
)

MODES = (("map", {}), ("vmap", {}), ("sched", {"lanes": 4, "chunk": 512}))

# Sched geometry frontier: wall time per (lanes, chunk).  It shows where each
# knob stops paying — refill overhead at tiny chunks, straggler overshoot at
# huge ones.
SCHED_FRONTIER = tuple((lanes, chunk)
                       for lanes in (1, 2, 4, 8) for chunk in (64, 512))
SCHED_FRONTIER_SMOKE = ((2, 64), (4, 512))


def _time_sweep(programs, kw, mode, mode_kw, repeats) -> tuple[float, dict]:
    """Median wall of ``repeats`` timed runs, compile excluded via warmup."""
    out = engine.run_sweep(programs, mode=mode, **mode_kw, **kw)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = engine.run_sweep(programs, mode=mode, **mode_kw, **kw)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], out


def run(smoke: bool = False, repeats: int = 3,
        json_path: str | None = None) -> dict:
    backend = jax.default_backend()
    cells = SMOKE_CELLS if smoke else SKEWED_CELLS
    programs, kw = pack_engine_cells(cells, seeds=1, collect_latency=True)

    clock = f"host_wall_on_{backend}"
    walls: dict[str, float] = {}
    reference = None
    for mode, mode_kw in MODES:
        walls[mode], out = _time_sweep(programs, kw, mode, mode_kw, repeats)
        emit(f"bench_engine/{mode}/wall_ms", f"{walls[mode] * 1e3:.1f}",
             f"{clock} median_of_{repeats} "
             + " ".join(f"{k}={v}" for k, v in mode_kw.items()))
        if reference is None:
            reference = out
        else:  # the drivers must agree bit for bit
            for key in ("acquisitions", "events", "grant_value", "lat_hist"):
                assert np.array_equal(reference[key], out[key]), (mode, key)

    events = reference["events"]
    emit("bench_engine/sum_events", int(events.sum()),
         f"B={len(cells)} lane_steps_paid_by_map_sched")
    emit("bench_engine/max_events", int(events.max()),
         f"x B = {int(events.max()) * len(cells)} lane_steps_paid_by_vmap")
    pad_stats = reference["pad_stats"]
    for k in ("live_thread_frac", "live_prog_frac", "live_mem_frac"):
        emit(f"bench_engine/pad/{k}", f"{pad_stats[k]:.3f}",
             "padded_batch_fraction_doing_real_work")

    # Sweep-wide acquire-latency tail (log2 histograms summed over cells);
    # the trajectory JSON carries the columns so latency regressions show
    # up next to the wall-clock ones.
    lat_hist = np.asarray(reference["lat_hist"]).sum(axis=0)
    latency = {f"lat_p{tag}": hist_percentile(lat_hist, q)
               for tag, q in (("50", 0.5), ("99", 0.99), ("999", 0.999))}
    for k, v in latency.items():
        emit(f"bench_engine/{k}", f"{v:.0f}", "cycles_acquire_to_grant")

    speedups = {}
    for a, b in (("sched", "vmap"), ("map", "vmap"), ("map", "sched")):
        speedups[f"{a}_over_{b}"] = walls[b] / walls[a]
        emit(f"bench_engine/speedup/{a}_over_{b}",
             f"{speedups[f'{a}_over_{b}']:.2f}",
             f"{clock} ratio (>1 means first is faster)")

    # Geometry frontier: every row re-checks bit-identity (frontier points
    # are alternate geometries of the same driver, not new semantics).
    frontier = []
    for lanes, chunk in SCHED_FRONTIER_SMOKE if smoke else SCHED_FRONTIER:
        wall, out = _time_sweep(programs, kw, "sched",
                                {"lanes": lanes, "chunk": chunk},
                                max(1, repeats - 1))
        assert np.array_equal(reference["grant_value"],
                              out["grant_value"]), (lanes, chunk)
        emit(f"bench_engine/frontier/sched/{lanes}x{chunk}/wall_ms",
             f"{wall * 1e3:.1f}", f"{clock} lanes={lanes} chunk={chunk}")
        frontier.append({"backend": backend, "mode": "sched",
                         "lanes": lanes, "chunk": chunk,
                         "wall_ms": round(wall * 1e3, 1)})

    point = {
        "backend": backend,
        "n_cells": len(cells),
        "smoke": smoke,
        "sum_events": int(events.sum()),
        "max_events": int(events.max()),
        "pad_stats": {k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in pad_stats.items()},
        "clock": clock,
        "wall_ms": {m: round(w * 1e3, 1) for m, w in walls.items()},
        "speedup": {k: round(v, 3) for k, v in speedups.items()},
        "latency": {k: round(v, 1) for k, v in latency.items()},
        "sched_params": dict(MODES[2][1]),
        "frontier": frontier,
    }
    if json_path:
        os.makedirs(os.path.dirname(json_path) or ".", exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(point, f, indent=1)
    return point


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep (CI-sized)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="where to write the trajectory point")
    args = ap.parse_args()
    run(smoke=args.smoke, repeats=args.repeats, json_path=args.json)


if __name__ == "__main__":
    main()
