"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the JAX profiler and reports the per-layer metrics, the
device's busy time and a breakdown. Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, require=harness.require_tpu, t0: float = T0) -> int:
    args = parse(argv)
    # run_sweep appends every result to a store when this is set: a write
    # inside the window that users of the sweep do not all pay
    os.environ.pop("REPRO_RESULTS_STORE", None)
    # libtpu writes its logs under /tmp unless this names a directory that
    # exists before the TPU runtime starts
    logs = harness.BENCH / "out" / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ["TPU_LOG_DIR"] = str(logs)
    stamps = {"python_s": time.perf_counter() - t0}
    try:
        benchmark = harness.load_json(harness.ROOT / "BENCHMARK.json")
        cell = harness.load_cell(args.workload)
        metrics = harness.cell_metrics(benchmark, bool(args.trace))
        mark = time.perf_counter()
        harness.use_checkout_cache()
        stamps["jax_import_s"] = time.perf_counter() - mark
        mark = time.perf_counter()
        devices = require(cell["chips"])
        stamps["tpu_init_s"] = time.perf_counter() - mark
        runner = harness.load_module(
            harness.BENCH / "runners" / f"{cell['runner']}.py",
            f"bench_runner_{cell['runner']}")
    except (harness.Refused, OSError, ImportError, KeyError) as e:
        print(f"bench: refused: {e!r}", file=sys.stderr)
        return 2
    counter = harness.CompileCounter()
    run = runner.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t0=t0, devices=devices,
                     counter=counter)
    device = run["device"]
    breakdown = None
    if args.trace:
        device = dict(device, busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        breakdown = run["trace"]["breakdown"]
    line = harness.result_line(
        correct=run["correct"], attempted=run["attempted"],
        failed=run["failed"], metrics=harness.read_metrics(metrics, run),
        device=device, breakdown=breakdown, checks=run["checks"])
    stamps["warm_s"] = run["warm_s"]
    print("setup " + json.dumps(dict(stamps, setup_s=run["setup_s"],
                                     jax=counter.setup)), file=sys.stderr)
    print("window " + json.dumps({k: run[k] for k in (
        "window_s", "sweeps", "events", "sweep_s", "sweep_events", "mode",
        "compiles_in_window", "check_s")}), file=sys.stderr)
    harness.print_checks(run["checks"])
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
