"""The control: the reference with one stated guarantee broken, in the
program's place, through the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--sweeps K]

For each seed it builds the cells of the first ``K`` sweeps that a run with
that seed times, at the cell's own size, runs them through the reference
with ``eager_store`` (a plain store visible at issue, not at commit), and
prints the numbers the comparison reads, one JSON line per seed. Every line
has to read as not correct. It runs on the host alone; the benchmark's own
runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

sys.path.insert(0, str(harness.ROOT / "src"))

MUTATION = ("eager_store",)


def window_rows(cell: dict, runner, ref, seed: int, sweeps: int) -> list[dict]:
    """The cells of a run's first sweeps, each with the reference's events
    and the driver that ``run_sweep`` picks for them on a TPU."""
    from repro.sim.engine import choose_mode
    params = cell["config_file"]["sweep"]
    n_seeds = cell["traffic_file"]["seeds_per_sweep"]
    out = []
    for k in range(sweeps):
        rows = []
        for lock in params["locks"]:
            for t in cell["traffic_file"]["threads"]:
                for s in runner.sweep_seeds(seed, k, n_seeds):
                    r = ref.run_cell(lock=lock, n_threads=t, seed=s,
                                     sweep=params)
                    rows.append({"lock": lock, "n_threads": t, "seed": s,
                                 "events": r["events"]})
        threads = [r["n_threads"] for r in rows]
        mode = choose_mode("tpu", n_cells=len(rows), n_threads=max(threads),
                           horizon=params["horizon"], n_active=threads)
        out.append({"rows": [dict(r, mode=mode) for r in rows]})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--sweeps", type=int, default=1)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    runner = harness.load_module(
        harness.BENCH / "runners" / f"{cell['runner']}.py", "bench_runner")
    ref = harness.load_module(harness.BENCH / "reference" / "lockvm.py",
                              "bench_ref_lockvm")
    for seed in args.seeds:
        t = time.perf_counter()
        sweeps = window_rows(cell, runner, ref, seed, args.sweeps)
        checks = runner.compare(cell, sweeps, seed, mutate=MUTATION)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": MUTATION[0],
            "correct": all(c["value"] <= c["limit"] for c in checks),
            "checks": checks, "seconds": time.perf_counter() - t}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
