"""Fuzz smoke — the ``sim.check`` differential fuzzer as a benchmark suite.

Three stages:

  1. **Differential smoke** — a small deterministic batch (composed lock
     scenarios + random ISA programs) through the oracle and the three
     engine sweep drivers, asserting zero differential/invariant failures,
     then mutation self-tests (``eager_store``, through BOTH the
     sequential and the batch oracle path) proving the checker still
     catches what it claims to catch.
  2. **Batch-oracle gate** — the sequential oracle and the batch oracle
     both run a fresh ≥1000-case batch (traces on — the fuzz config);
     every stat, trace row and exit reason must agree bit for bit there
     AND over the checked-in ``tests/corpus``, and the batch path must be
     ≥ ``SPEEDUP_GATE``× the sequential cases/sec.  Timing runs with the
     GC disabled (standard ``timeit`` practice — JAX registers a gc
     callback that otherwise adds multi-ms pauses at random points).
  3. **`BENCH_fuzz.json`** — both throughputs, the ratio, and the
     divergence counts, uploaded alongside ``BENCH_engine.json`` so fuzz
     perf joins the benchmark trajectory.

The full steered run with a per-CI-run seed lives in the workflows
(``python -m repro.sim.check --cases ... --batch-oracle --steer``); this
suite is the always-on canary + ratio gate inside ``benchmarks.run``.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import time

import numpy as np

from repro.sim.check import (MODES, fuzz, generate_batch, load_scenario,
                             run_batch_oracle, run_oracle_case)
from repro.sim.check import _fastcase
from repro.sim.check.runner import STAT_KEYS

from .common import emit

CASES = 48
SMOKE_CASES = 24  # 14/0.6 threshold: every SIM_LOCKS entry composed once
SEED = 20260731

# Batch-oracle gate config (the "CI CPU fuzz config"): fresh-batch size,
# required batch/sequential throughput ratio, and timing repeats.
BENCH_CASES = 1000
SMOKE_BENCH_CASES = 300
SPEEDUP_GATE = 50.0
BATCH_REPEATS = 5

CORPUS_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "tests",
                          "corpus")


def _diff_case(stats_a, trace_a, stats_b, trace_b) -> bool:
    """True when the two oracle runs differ in any stat or trace bit."""
    for k in STAT_KEYS:
        if not np.array_equal(np.asarray(stats_a[k]), np.asarray(stats_b[k])):
            return True
    return (trace_a.acquires != trace_b.acquires
            or trace_a.fadds != trace_b.fadds
            or trace_a.exit_reason != trace_b.exit_reason)


def _count_divergences(scenarios, seq_runs, bres) -> int:
    return sum(_diff_case(seq_runs[i][0], seq_runs[i][1],
                          bres.stats[i], bres.traces[i])
               for i in range(len(scenarios)))


def _corpus_divergences() -> tuple[int, int]:
    """(entries, divergences) of batch vs sequential over tests/corpus."""
    paths = sorted(glob.glob(os.path.join(CORPUS_DIR, "*.npz")))
    n_div = 0
    for p in paths:
        s = load_scenario(p)
        seq = [run_oracle_case(s)]
        bres = run_batch_oracle([s])
        n_div += _count_divergences([s], seq, bres)
    return len(paths), n_div


def run(smoke: bool = False, json_path: str | None = None) -> dict:
    n_cases = SMOKE_CASES if smoke else CASES
    scenarios = generate_batch(n_cases, SEED)
    t0 = time.time()
    # oracle vs map/vmap/sched (randomized sched lane geometry) +
    # invariants
    report = fuzz(scenarios, sched_seed=SEED)
    dt = time.time() - t0
    emit("fuzz/cases", n_cases,
         f"composed+random, seed={SEED}, modes={'/'.join(MODES)}")
    emit("fuzz/oracle_events", report.total_events,
         f"{report.total_events / max(dt, 1e-9):,.0f} events/s")
    emit("fuzz/failures", len(report.failures),
         "differential+invariants" if report.ok else report.summary())
    assert report.ok, report.summary()

    # mutation self-test: an injected store-visibility bug MUST be caught —
    # through the sequential oracle AND through the batch-oracle path
    mutated = fuzz(scenarios, modes=("map",),
                   oracle_mutate=("eager_store",))
    emit("fuzz/mutation_caught", len(mutated.failures),
         "eager_store self-test (must be > 0)")
    assert not mutated.ok, "eager_store mutation was not caught"
    mutated_b = fuzz(scenarios, modes=("map",),
                     oracle_mutate=("eager_store",), batch_oracle=True)
    emit("fuzz/mutation_caught_batch", len(mutated_b.failures),
         "eager_store through the batch oracle (must be > 0)")
    assert not mutated_b.ok, "eager_store not caught via batch oracle"

    # ---- batch-oracle throughput gate + bit-identity sweep ----
    bench_cases = SMOKE_BENCH_CASES if smoke else BENCH_CASES
    bench = generate_batch(bench_cases, SEED + 1)
    gc_was = gc.isenabled()
    gc.disable()
    try:
        t0 = time.time()
        seq_runs = [run_oracle_case(s) for s in bench]
        seq_dt = time.time() - t0
        # one untimed warmup (first call pays library page-in + allocator
        # growth), then fastest-of-N — the timeit rationale: slower repeats
        # measure scheduler noise, not the code
        bres = run_batch_oracle(bench, collect_trace=True,
                                collect_coverage=True)
        batch_dts = []
        for _ in range(BATCH_REPEATS):
            t0 = time.time()
            bres = run_batch_oracle(bench, collect_trace=True,
                                    collect_coverage=True)
            batch_dts.append(time.time() - t0)
        batch_dt = min(batch_dts)
    finally:
        if gc_was:
            gc.enable()
    divergences = _count_divergences(bench, seq_runs, bres)
    n_corpus, corpus_div = _corpus_divergences()
    seq_cps = bench_cases / seq_dt
    batch_cps = bench_cases / batch_dt
    speedup = batch_cps / seq_cps
    impl = "c" if _fastcase.HAVE_FAST else "numpy"
    emit("fuzz/seq_cases_per_sec", f"{seq_cps:.1f}",
         f"sequential oracle, {bench_cases} cases, traces on")
    emit("fuzz/batch_cases_per_sec", f"{batch_cps:.1f}",
         f"batch oracle (impl={impl}), traces+coverage on, "
         f"fastest of {BATCH_REPEATS}")
    emit("fuzz/batch_speedup", f"{speedup:.1f}",
         f"gate >= {SPEEDUP_GATE}x")
    emit("fuzz/batch_divergences", divergences,
         f"vs sequential over the {bench_cases}-case fresh batch")
    emit("fuzz/corpus_divergences", corpus_div,
         f"vs sequential over {n_corpus} tests/corpus entries")
    assert divergences == 0, \
        f"{divergences} batch-vs-sequential divergences on the fresh batch"
    assert corpus_div == 0, \
        f"{corpus_div} batch-vs-sequential divergences on tests/corpus"
    assert impl == "c", \
        "no C compiler found — the batch-oracle fast path (and with it " \
        "the throughput gate) is unavailable"
    assert speedup >= SPEEDUP_GATE, \
        f"batch oracle {speedup:.1f}x sequential, gate {SPEEDUP_GATE}x"

    point = {
        "suite": "fuzz_smoke",
        "config": {"bench_cases": bench_cases, "seed": SEED + 1,
                   "smoke": smoke, "traces": True, "coverage": True,
                   "batch_impl": impl, "batch_repeats": BATCH_REPEATS},
        "sequential_cases_per_sec": round(seq_cps, 2),
        "batch_cases_per_sec": round(batch_cps, 2),
        "speedup": round(speedup, 2),
        "speedup_gate": SPEEDUP_GATE,
        "divergences_fresh_batch": divergences,
        "divergences_corpus": corpus_div,
        "corpus_entries": n_corpus,
        "smoke_failures": len(report.failures),
        "mutation_caught": len(mutated.failures),
        "mutation_caught_batch": len(mutated_b.failures),
    }
    if json_path:
        os.makedirs(os.path.dirname(json_path) or ".", exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(point, f, indent=1)
        emit("fuzz/json", json_path, "BENCH_fuzz.json artifact")
    return point


if __name__ == "__main__":
    ap = argparse.ArgumentParser(prog="python -m benchmarks.fuzz_smoke")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default="BENCH_fuzz.json",
                    help="write the throughput/divergence point here")
    args = ap.parse_args()
    run(smoke=args.smoke, json_path=args.json)
