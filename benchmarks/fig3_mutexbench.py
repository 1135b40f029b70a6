"""Paper Figure 3 — MutexBench: aggregate lock throughput vs thread count.

CS = 4 PRNG steps, NCS uniform in [0,200) steps (paper §4.2), on the lockVM.
The sweep collects latency: alongside each throughput point the figure
reports the contended acquire tail (lat_p50/p99/p999, cycles) — the
paper-relevant columns the ROADMAP names after fig7.
Claims validated (tests/test_sim_paper_claims.py):
  * ticket best at low T, collapses at high T;
  * TWA ≈ ticket at low T, ≥ MCS at high T.
Also runs the appendix variants (tkt-dual, twa-id, twa-staged, partitioned),
the queue-lock baselines (anderson, clh, hemlock — Fissile Locks), the
waiting-array counting semaphore (twa-sem, permits=4), and the PR-5
compositions (fissile-twa fusion, twa-rw reader-writer at the default 50%
read mix).  ``twa-timo`` stays out: its abandonment ring has
``TIMO_RING`` (32) slots, too few for 64 threads; fig12deg covers it.  The
whole figure — 13 locks × 7 thread counts × 3 seeds — is ONE SweepSpec and
one compiled engine call.
"""

from __future__ import annotations

import numpy as np

from repro.sim import SIM_LOCKS
from repro.sim.workloads import DEFAULT_HORIZON, SweepSpec, run_sweep

from .common import emit

THREADS = (1, 2, 4, 8, 16, 32, 64)
LOCKS = tuple(lk for lk in SIM_LOCKS if lk != "twa-timo")


def spec(locks=LOCKS, threads=THREADS, runs: int = 3,
         horizon: int = DEFAULT_HORIZON) -> SweepSpec:
    """The figure's sweep: every lock × thread count × seed."""
    return SweepSpec(locks=tuple(locks), threads=tuple(threads),
                     seeds=tuple(range(1, runs + 1)), cs_work=4, ncs_max=200,
                     collect_latency=True, horizon=horizon)


def run(locks=LOCKS, threads=THREADS, runs: int = 3) -> dict:
    results = run_sweep(spec(locks, threads, runs))
    by_cell = {}
    for r in results:
        by_cell.setdefault((r["lock"], r["n_threads"]), []).append(r)
    curves = {}
    for lock in locks:
        curves[lock] = []
        for t in threads:
            rs = by_cell[(lock, t)]
            tp = float(np.median([r["throughput"] for r in rs]))
            curves[lock].append(tp)
            emit(f"fig3/{lock}/threads={t}", f"{tp:.6f}", "acq_per_cycle")
            for col in ("lat_p50", "lat_p99", "lat_p999"):
                v = float(np.median([r[col] for r in rs]))
                emit(f"fig3/{lock}/threads={t}/{col}", f"{v:.0f}", "cycles")
    t64 = {k: v[-1] for k, v in curves.items()}
    emit("fig3/twa_over_ticket@64", f"{t64['twa'] / t64['ticket']:.3f}",
         "paper: >>1")
    emit("fig3/twa_over_mcs@64", f"{t64['twa'] / t64['mcs']:.3f}",
         "paper: >=1")
    return curves


if __name__ == "__main__":
    run()
