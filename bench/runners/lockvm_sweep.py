"""Runner for lockVM sweep cells: a whole figure of lock x threads x seeds.

Set-up builds the cell's ``SweepSpec`` from its configuration and traffic
files and warms up one sweep at horizon 1 on the same shapes (horizon and
seeds are data, so the timed sweeps compile nothing). The window then calls
the user entry ``repro.sim.workloads.run_sweep(spec)`` with ``mode="auto"``
again and again; sweep ``k`` draws its seeds from ``--seed`` and ``k``, so
no two sweeps repeat. It starts no sweep that the last sweep's length says
would end past ``--seconds``, and always runs one.

Once the window has closed, a sample of its cells drawn from the seed, the
longest among them, is run again by the plain reference
(``bench/reference/lockvm.py``) and every number of each is compared
exactly. The sample reaches every lane of the driver that ran them (see
:func:`sample`).
"""

from __future__ import annotations

import math
import shutil
import time
from dataclasses import replace

import numpy as np

from harness import BENCH, device_info, load_module

CHECK_CELLS = 16          # cells of a sched window compared with the reference
TRACE_DIR = BENCH / "out" / "trace"

# what the reference reports for a cell, compared exactly with the sweep's row
COMPARED = ("acquisitions", "waited_acquisitions", "handover_sum",
            "handover_count", "events", "sleeping", "mem", "throughput",
            "avg_handover", "fault_schedule", "lat_hist", "lat_p50", "lat_p99",
            "lat_p999")


def sweep_seeds(seed: int, k: int, n: int) -> tuple[int, ...]:
    """The ``n`` cell seeds of sweep ``k`` (uint32, as the lockVM takes them)."""
    state = np.random.SeedSequence([seed & (2**64 - 1), k]).generate_state(n)
    return tuple(int(s) for s in state)


def sweep_spec(cell: dict, seeds: tuple[int, ...]):
    from repro.sim.costs import Costs
    from repro.sim.workloads import SweepSpec
    params = dict(cell["config_file"]["sweep"])
    params["costs"] = Costs(**params["costs"])
    params["locks"] = tuple(params["locks"])
    return SweepSpec(threads=tuple(cell["traffic_file"]["threads"]),
                     seeds=seeds, **params)


def _annotate(trace: bool, name: str):
    import contextlib
    if not trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def run(cell: dict, *, seed: int, seconds: float, trace: bool, t0: float,
        devices, counter) -> dict:
    import jax
    from repro.sim.workloads import run_sweep

    imported = time.perf_counter()
    n_seeds = cell["traffic_file"]["seeds_per_sweep"]
    warm = replace(sweep_spec(cell, sweep_seeds(seed, 2**32, n_seeds)),
                   horizon=1)
    run_sweep(warm)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    counter.active = True
    sweeps = []
    start = time.perf_counter()
    setup_s = start - t0
    while True:
        with _annotate(trace, "bench.seeds"):
            spec = sweep_spec(cell, sweep_seeds(seed, len(sweeps), n_seeds))
        with _annotate(trace, "bench.run_sweep"):
            a = time.perf_counter_ns()
            rows = run_sweep(spec)
            b = time.perf_counter_ns()
        sweeps.append({"spec": spec, "rows": rows, "start_ns": a, "end_ns": b,
                       "events": sum(int(r["events"]) for r in rows)})
        if (b * 1e-9 - start) + (b - a) * 1e-9 > seconds:
            break
    window_s = (sweeps[-1]["end_ns"] - sweeps[0]["start_ns"]) * 1e-9
    counter.active = False
    if trace:
        jax.profiler.stop_trace()

    device = device_info(devices)
    out = {
        "setup_s": setup_s,
        "warm_s": start - imported,
        "window_s": window_s,
        "events": sum(s["events"] for s in sweeps),
        "sweeps": len(sweeps),
        "sweep_s": [(s["end_ns"] - s["start_ns"]) * 1e-9 for s in sweeps],
        "sweep_events": [s["events"] for s in sweeps],
        "mode": sweeps[0]["rows"][0]["mode"],
        "compiles_in_window": counter.count,
        "attempted": sum(len(s["rows"]) for s in sweeps),
        # a cell that stopped on its event cap, not its horizon, was cut short
        "failed": sum(int(r["events"]) >= s["spec"].max_events
                      for s in sweeps for r in s["rows"]),
        "device": device,
        "trace": None,
    }
    if trace:
        reduce = load_module(BENCH / "trace.py", "bench_trace")
        out["trace"] = reduce.reduce_dir(TRACE_DIR,
                                         [s["events"] for s in sweeps])
    mark = time.perf_counter()
    checks = compare(cell, sweeps, seed)
    out["check_s"] = time.perf_counter() - mark
    out["checks"] = checks
    out["correct"] = all(c["value"] <= c["limit"] for c in checks)
    return out


def sample(sweeps: list[dict], seed: int, n: int) -> list[tuple[int, int]]:
    """(sweep, row) pairs to compare, drawn from the seed, the longest first.

    Under ``vmap`` row ``j`` of every sweep runs on lane ``j``, so a fault
    in one lane spoils the same row of each sweep: the sample is then every
    row of one sweep. Under ``sched`` a cell takes whichever of the lanes
    frees up first, so each lane runs about a quarter of the cells and ``n``
    cells reach every lane.
    """
    pairs = [(i, j) for i, s in enumerate(sweeps) for j in range(len(s["rows"]))]
    longest = max(pairs, key=lambda p: int(sweeps[p[0]]["rows"][p[1]]["events"]))
    rng = np.random.default_rng([seed & (2**64 - 1), 0xC4EC])
    if sweeps[0]["rows"][0].get("mode") == "vmap":
        k = int(rng.integers(len(sweeps)))
        rest = [(k, j) for j in range(len(sweeps[k]["rows"]))]
        return [longest] + [p for p in rest if p != longest]
    rest = [p for p in pairs if p != longest]
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def _plain(v):
    if hasattr(v, "to_lists"):
        return v.to_lists()
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    return v


def _same(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float) and math.isnan(a):
        return math.isnan(b)
    return a == b


def mismatches(row: dict, expected: dict) -> list[str]:
    """The compared keys in which a sweep's row differs from the reference."""
    return [k for k in COMPARED
            if k in expected and not _same(expected[k], _plain(row.get(k)))]


def compare(cell: dict, sweeps: list[dict], seed: int,
            mutate: tuple = ()) -> list[dict]:
    """Run the reference over a sample of the window's cells and compare.

    ``mutate`` is handed to the reference; a non-empty one makes the
    reference the control, which has to fail this comparison.
    """
    ref = load_module(BENCH / "reference" / "lockvm.py", "bench_ref_lockvm")
    sweep_params = cell["config_file"]["sweep"]
    picks = sample(sweeps, seed, CHECK_CELLS)
    bad_cells = bad_values = 0
    for i, j in picks:
        row = sweeps[i]["rows"][j]
        got = row if not mutate else ref.run_cell(
            lock=row["lock"], n_threads=int(row["n_threads"]),
            seed=int(row["seed"]), sweep=sweep_params, mutate=mutate)
        expected = ref.run_cell(lock=row["lock"], n_threads=int(row["n_threads"]),
                                seed=int(row["seed"]), sweep=sweep_params)
        bad = mismatches(got, expected)
        bad_cells += bool(bad)
        bad_values += len(bad)
    return [{"name": "mismatched_cells", "value": bad_cells, "limit": 0,
             "of": len(picks)},
            {"name": "mismatched_values", "value": bad_values, "limit": 0,
             "of": len(picks) * len(COMPARED)}]
