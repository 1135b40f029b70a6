"""Runner for lockVM pool cells: the TWA paper's Figure 2, a pass of sweeps
over pools of locks that share one waiting array or hold one each.

Set-up builds one ``SweepSpec`` per pool of the traffic file (the
configuration's ``sweep`` with ``n_locks`` the pool) and warms each up at
horizon 1 on the same shapes, so the timed sweeps compile nothing. A pass
of the window calls the user entry ``repro.sim.workloads.run_sweep(spec)``
with ``mode="auto"`` once per pool, in the traffic's order, each call in
its own ``bench.run_sweep`` span; the sweep of pool ``n`` in pass ``k``
draws its seeds from ``--seed``, ``k`` and ``n``. The window runs whole
passes: it starts no pass that the last one's length says would end past
``--seconds``, and always runs one.

After the window, each pool's median throughput of the shared array over
that of the private arrays goes to stderr (Figure 2's own output, not a
metric), and a sample of the window's cells is run again by the plain
reference (``bench/reference/lockvm_pool.py``), every number of each
compared exactly (see :func:`sample`).

The control, the reference with one stated guarantee broken in the
program's place, runs on the host alone:

    python3 bench/runners/lockvm_pool.py --workload <cell> --seeds <n> ...

It prints one JSON line per seed and mutation, each of which has to read
as not correct.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import BENCH, device_info, load_cell, load_module  # noqa: E402

single = load_module(BENCH / "runners" / "lockvm_sweep.py",
                     "bench_runner_lockvm_sweep")

# each breaks one guarantee of the configuration; the comparison has to
# reject every one: a plain store visible at issue, and the private arm's
# locks hashing into one array
MUTATIONS = ("eager_store", "shared_hash")


def pool_seeds(seed: int, k: int, pool: int, n: int) -> tuple[int, ...]:
    """The ``n`` cell seeds of pool ``pool`` in pass ``k`` (uint32)."""
    state = np.random.SeedSequence([seed & (2**64 - 1), k, pool])
    return tuple(int(s) for s in state.generate_state(n))


def pool_spec(cell: dict, pool: int, seeds: tuple[int, ...]):
    """The sweep of one pool: the cell's sweep over ``pool`` locks."""
    return replace(single.sweep_spec(cell, seeds), n_locks=pool)


def run(cell: dict, *, seed: int, seconds: float, trace: bool, t0: float,
        devices, counter) -> dict:
    import jax
    from repro.sim.workloads import run_sweep

    imported = time.perf_counter()
    pools = cell["traffic_file"]["pools"]
    n_seeds = cell["traffic_file"]["seeds_per_sweep"]
    for pool in pools:
        run_sweep(replace(pool_spec(cell, pool,
                                    pool_seeds(seed, 2**32, pool, n_seeds)),
                          horizon=1))

    if trace:
        shutil.rmtree(single.TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(single.TRACE_DIR))
    counter.active = True
    sweeps = []
    start = time.perf_counter()
    setup_s = start - t0
    k = 0
    while True:
        first = time.perf_counter_ns()
        for pool in pools:
            with single._annotate(trace, "bench.seeds"):
                spec = pool_spec(cell, pool,
                                 pool_seeds(seed, k, pool, n_seeds))
            with single._annotate(trace, "bench.run_sweep"):
                a = time.perf_counter_ns()
                rows = run_sweep(spec)
                b = time.perf_counter_ns()
            sweeps.append({"pool": pool, "spec": spec, "rows": rows,
                           "start_ns": a, "end_ns": b,
                           "events": sum(int(r["events"]) for r in rows)})
        k += 1
        if (b * 1e-9 - start) + (b - first) * 1e-9 > seconds:
            break
    window_s = (sweeps[-1]["end_ns"] - sweeps[0]["start_ns"]) * 1e-9
    counter.active = False
    if trace:
        jax.profiler.stop_trace()

    print("figure2 " + json.dumps(shared_over_private(sweeps)),
          file=sys.stderr, flush=True)
    out = {
        "setup_s": setup_s,
        "warm_s": start - imported,
        "window_s": window_s,
        "events": sum(s["events"] for s in sweeps),
        "sweeps": len(sweeps),
        "sweep_s": [(s["end_ns"] - s["start_ns"]) * 1e-9 for s in sweeps],
        "sweep_events": [s["events"] for s in sweeps],
        "mode": ",".join(dict.fromkeys(s["rows"][0]["mode"] for s in sweeps)),
        "compiles_in_window": counter.count,
        "attempted": sum(len(s["rows"]) for s in sweeps),
        # a cell that stopped on its event cap, not its horizon, was cut short
        "failed": sum(int(r["events"]) >= s["spec"].max_events
                      for s in sweeps for r in s["rows"]),
        "device": device_info(devices),
        "trace": None,
    }
    if trace:
        reduce = load_module(BENCH / "trace.py", "bench_trace")
        out["trace"] = reduce.reduce_dir(single.TRACE_DIR,
                                         [s["events"] for s in sweeps])
    mark = time.perf_counter()
    checks = compare(cell, sweeps, seed)
    out["check_s"] = time.perf_counter() - mark
    out["checks"] = checks
    out["correct"] = all(c["value"] <= c["limit"] for c in checks)
    return out


def shared_over_private(sweeps: list[dict]) -> dict[str, float]:
    """Each pool's median throughput with the shared array over that with
    private arrays, over every cell of the window."""
    out = {}
    for pool in dict.fromkeys(s["pool"] for s in sweeps):
        rows = [r for s in sweeps if s["pool"] == pool for r in s["rows"]]
        shared, private = (
            np.median([r["throughput"] for r in rows
                       if bool(r["private_arrays"]) == arm])
            for arm in (False, True))
        out[str(pool)] = float(shared / private)
    return out


def sample(sweeps: list[dict], seed: int) -> list[tuple[int, int]]:
    """(sweep, row) pairs to compare: for each pool, the cell of the most
    events among that pool's sweeps, then every row of one of them drawn
    from the seed.

    Under ``vmap`` row ``j`` of every sweep runs on lane ``j``, so a fault
    in one lane spoils the same row of each sweep; every row of a sweep
    reaches every lane, and both arms.
    """
    rng = np.random.default_rng([seed & (2**64 - 1), 0xC4EC])
    picks = []
    for pool in dict.fromkeys(s["pool"] for s in sweeps):
        mine = [i for i, s in enumerate(sweeps) if s["pool"] == pool]
        longest = max(((i, j) for i in mine
                       for j in range(len(sweeps[i]["rows"]))),
                      key=lambda p: int(sweeps[p[0]]["rows"][p[1]]["events"]))
        k = mine[int(rng.integers(len(mine)))]
        picks += [longest] + [(k, j) for j in range(len(sweeps[k]["rows"]))
                              if (k, j) != longest]
    return picks


def reference(ref, row: dict, params: dict, mutate: tuple = ()) -> dict:
    return ref.run_cell(lock=row["lock"], n_threads=int(row["n_threads"]),
                        seed=int(row["seed"]), sweep=params,
                        n_locks=int(row["n_locks"]),
                        private_arrays=bool(row["private_arrays"]),
                        mutate=mutate)


def compare(cell: dict, sweeps: list[dict], seed: int,
            mutate: tuple = ()) -> list[dict]:
    """Run the reference over a sample of the window's cells and compare.

    A non-empty ``mutate`` makes the reference the control, which has to
    fail this comparison: the rows are then the reference's own (see
    :func:`control_sweeps`), and the mutated reference stands in for the
    program.
    """
    ref = load_module(BENCH / "reference" / "lockvm_pool.py",
                      "bench_ref_lockvm_pool")
    params = cell["config_file"]["sweep"]
    picks = sample(sweeps, seed)
    bad_cells = bad_values = 0
    for i, j in picks:
        row = sweeps[i]["rows"][j]
        if mutate:
            got, expected = reference(ref, row, params, mutate), row
        else:
            got, expected = row, reference(ref, row, params)
        bad = single.mismatches(got, expected)
        bad_cells += bool(bad)
        bad_values += len(bad)
    return [{"name": "mismatched_cells", "value": bad_cells, "limit": 0,
             "of": len(picks)},
            {"name": "mismatched_values", "value": bad_values, "limit": 0,
             "of": len(picks) * len(single.COMPARED)}]


def control_sweeps(cell: dict, seed: int) -> list[dict]:
    """The sweeps of a run's first pass at the cell's own size, each row
    the reference's result for the cell with the driver that ``run_sweep``
    picks for it on a TPU."""
    from repro.sim.engine import choose_mode
    ref = load_module(BENCH / "reference" / "lockvm_pool.py",
                      "bench_ref_lockvm_pool")
    params = cell["config_file"]["sweep"]
    threads = cell["traffic_file"]["threads"]
    n_seeds = cell["traffic_file"]["seeds_per_sweep"]
    out = []
    for pool in cell["traffic_file"]["pools"]:
        rows = [{"lock": lock, "n_threads": t, "seed": s, "n_locks": pool,
                 "private_arrays": arm}
                for lock in params["locks"] for t in threads
                for s in pool_seeds(seed, 0, pool, n_seeds)
                for arm in params["private_arrays"]]
        rows = [dict(r, **reference(ref, r, params)) for r in rows]
        active = [r["n_threads"] for r in rows]
        mode = choose_mode("tpu", n_cells=len(rows), n_threads=max(active),
                           horizon=params["horizon"], n_active=active)
        out.append({"pool": pool, "rows": [dict(r, mode=mode) for r in rows]})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="The control of a pool cell: the reference with one "
                    "guarantee broken, through the comparison.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    cell = load_cell(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        sweeps = control_sweeps(cell, seed)
        for mutation in MUTATIONS:
            checks = compare(cell, sweeps, seed, mutate=(mutation,))
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": mutation,
                "correct": all(c["value"] <= c["limit"] for c in checks),
                "checks": checks, "seconds": time.perf_counter() - t}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
