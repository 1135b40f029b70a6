"""Paper Figure 2 — Inter-Lock Interference.

64 threads, pool of L locks picked at random per iteration; reports the
throughput of shared-array TWA divided by an idealized private-array-per-lock
TWA.  The paper sweeps pools of 1 to 8 192 locks and reports a worst
penalty under 8%.  This script runs pools of 1, 8 and 64, as the chip
benchmark's ``interlock`` configuration does; CS 50 and NCS U[0,100) PRNG
steps are the repository's own settings
(``repro.sim.workloads.fig2_interlock_interference``).
"""

from __future__ import annotations

from repro.sim.workloads import fig2_interlock_interference

from .common import emit

# Each pool size compiles a fresh event engine (distinct simulated-memory
# shape) and the idealized private-array variant's memory grows linearly in
# the pool, so the CPU sweep stops where the collision trend is already
# established.
POOLS = (1, 8, 64)


def run(pools=POOLS) -> dict:
    ratios = fig2_interlock_interference(pools, runs=2, horizon=400_000)
    out = {}
    for n, ratio in zip(pools, ratios):
        emit(f"fig2/locks={n}", f"{ratio:.4f}", "shared_over_private")
        out[n] = ratio
    emit("fig2/worst_penalty", f"{1 - min(ratios):.4f}", "paper: <0.08")
    return out


if __name__ == "__main__":
    run()
