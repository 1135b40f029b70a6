"""sim.check — differential fuzzing & model checking for the lockVM.

Layers:
  * :mod:`oracle`       — a pure-NumPy sequential reference interpreter for
    the full ISA, executing the *same* packed program/layout arrays as
    ``sim.engine`` under the same :data:`engine.EVENT_ORDER_CONTRACT`.
  * :mod:`batch_oracle` — a vectorized lockstep interpreter (NumPy) plus a
    compiled per-case C fast path (:mod:`_fastcase`), both bit-identical to
    the sequential reference — the fuzz-scale throughput layer.
  * :mod:`generate`     — structured random generators: well-formed random
    ISA programs, random lock/thread/wa/permit/cost geometries, composed
    scenarios wrapping every ``SIM_LOCKS`` generator in randomized critical
    sections with shared occupancy counters, and coverage-steering
    mutations of promoted cases.
  * :mod:`coverage`     — per-case coverage signatures (opcode/branch/spin
    histograms, lock x invariant-class, wrap/collision events) accumulated
    into a run-level :class:`~repro.sim.check.coverage.CoverageMap`.
  * :mod:`invariants` + :mod:`runner` — oracle vs ``run_sweep`` differential
    execution (bit-identical stats across ``mode="map"/"vmap"/"sched"``,
    with per-case randomized sched lane geometry), engine-independent
    invariants (exclusion incl. the weighted rw probe, wrap-aware
    conservation/FIFO, per-thread liveness bounds, deadlock, collision),
    a greedy shrinker, coverage-guided steering, batched corpus replay,
    and a replayable ``.npz`` corpus format.

See README.md in this directory for the invariant catalog and the
reproduce/shrink workflow.
"""

from .batch_oracle import BatchOracleResult, run_batch_oracle
from .coverage import BUCKETS, CoverageMap, case_signature
from .generate import (PAD_LOCKS, PAD_MEM_WORDS, PAD_THREADS, Scenario,
                       gen_composed_scenario, gen_geometry,
                       gen_random_scenario, generate_batch, mutate_scenario,
                       scenario_faults, splice_programs, with_fault_schedule)
from .invariants import active_classes, check_invariants
from .oracle import ORACLE_MUTATIONS, Trace, run_oracle
from .runner import (MODES, SCHED_GEOMETRY_POOL, FuzzReport, SteerResult,
                     case_fails, case_problems, check_case,
                     count_instructions, failure_classes, fuzz,
                     load_scenario, replay_corpus, run_engine_batch,
                     run_oracle_case, save_scenario, sched_geometries,
                     shrink, steer)

__all__ = [
    "Scenario", "gen_geometry", "gen_random_scenario",
    "gen_composed_scenario", "generate_batch", "mutate_scenario",
    "scenario_faults", "splice_programs", "with_fault_schedule",
    "PAD_THREADS", "PAD_LOCKS", "PAD_MEM_WORDS",
    "run_oracle", "Trace", "ORACLE_MUTATIONS",
    "run_batch_oracle", "BatchOracleResult",
    "CoverageMap", "case_signature", "BUCKETS", "active_classes",
    "check_invariants", "check_case", "case_problems", "case_fails",
    "failure_classes", "fuzz", "FuzzReport", "shrink",
    "steer", "SteerResult", "replay_corpus",
    "count_instructions", "run_engine_batch", "run_oracle_case",
    "save_scenario", "load_scenario", "MODES",
    "sched_geometries", "SCHED_GEOMETRY_POOL",
]
