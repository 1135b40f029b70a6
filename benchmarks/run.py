"""Benchmark driver — one module per paper table/figure + framework-level
benchmarks.  Prints ``name,value,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,table1] [--smoke]
                                            [--results store.jsonl]

``--smoke`` asks each suite that supports it (fig8, fig9, fig10,
fig12deg, fuzz) for a reduced grid — CI runs these per-PR and uploads the
CSV as a workflow artifact.  ``--results PATH`` persists every figure's
sweep cells into the JSONL results store at PATH (the
``REPRO_RESULTS_STORE`` hook in ``repro.sim.workloads.run_sweep``), which
``python -m repro.sim.results`` then queries.
"""

from __future__ import annotations

import argparse
import inspect
import os
import time

from .common import emit, header

SUITES = [
    ("table1", "benchmarks.table1_complexity"),
    ("fig1", "benchmarks.fig1_invalidation_diameter"),
    ("fig2", "benchmarks.fig2_interlock_interference"),
    ("fig3", "benchmarks.fig3_mutexbench"),
    ("fig5", "benchmarks.fig5_throw"),
    ("fig6", "benchmarks.fig6_rrc"),
    ("fig7", "benchmarks.fig7_stress_latency"),
    ("fig8", "benchmarks.fig8_collisions"),
    ("fig9", "benchmarks.fig9_cost_grid"),
    ("fig10", "benchmarks.fig10_rw_scaling"),
    ("fig11", "benchmarks.fig11_locktorture"),
    ("fig12deg", "benchmarks.fig12_degradation"),
    ("fig13", "benchmarks.fig13_serve_e2e"),
    ("threads", "benchmarks.threads_microbench"),
    ("admission", "benchmarks.framework_admission"),
    ("bench_engine", "benchmarks.bench_engine"),
    ("fuzz", "benchmarks.fuzz_smoke"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated suite prefixes to run")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grids for suites that support it")
    ap.add_argument("--results", default="",
                    help="persist every sweep into this JSONL results store")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_checkout_cache
    use_checkout_cache()
    only = [s for s in args.only.split(",") if s]
    if args.results:
        from repro.sim.workloads import RESULTS_STORE_ENV
        os.environ[RESULTS_STORE_ENV] = args.results

    header()
    t_start = time.time()
    failures = []
    for name, module in SUITES:
        if only and not any(name.startswith(o) for o in only):
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            kw = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kw["smoke"] = True
            mod.run(**kw)
            emit(f"{name}/_elapsed_s", f"{time.time() - t0:.1f}", "ok")
        except Exception as e:  # keep the suite going; report at the end
            failures.append((name, repr(e)))
            emit(f"{name}/_elapsed_s", f"{time.time() - t0:.1f}",
                 f"FAILED: {e!r}")
    emit("run/_total_s", f"{time.time() - t_start:.1f}",
         f"failures={len(failures)}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
