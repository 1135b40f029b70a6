"""Replay the checked-in fuzz corpus (tests/corpus/*.npz) as fast tier-1
regression cases.

Two families, distinguished by the expected-class pin each entry carries:
  * ``diff_*``: scenarios historically shrunk under an injected oracle
    mutation (store visibility, lost wakeups, free invalidation).  On the
    correct engine they must replay with ZERO problems across all three
    sweep drivers — they pin exactly the
    engine behaviours those mutations would break.
  * ``inv_*``: deliberately broken lock programs.  The checker must KEEP
    reporting the recorded invariant classes — they pin the checker's own
    sensitivity (one historical shrunk case per invariant class:
    exclusion, conservation, deadlock, collision).
  * ``fault_*``: composed scenarios carrying scheduled fault injections
    (preemption windows, spurious wakeups, a thread abort, and a
    timed-lock abandonment case under preemption) whose every fault lands
    inside the run.  They must replay with ZERO problems across all three
    sweep drivers — they pin the fault semantics of the engine, both oracles
    and the C fast path against each other.

Regenerate with ``python -m repro.sim.check.make_corpus tests/corpus``
after any intended engine/oracle semantics change.
"""

import glob
import os

import pytest

from repro.sim.check import (MODES, case_problems, failure_classes,
                             load_scenario)

CORPUS_DIR = os.path.join(os.path.dirname(__file__), "corpus")
CORPUS = sorted(glob.glob(os.path.join(CORPUS_DIR, "*.npz")))


def test_corpus_is_present_and_covers_all_invariant_classes():
    assert CORPUS, "tests/corpus is empty — run make_corpus"
    names = [os.path.basename(p) for p in CORPUS]
    assert sum(n.startswith("diff_") for n in names) >= 3
    # near-wrap pins: tickets seeded at INT32_MAX-2 must replay clean
    assert sum(n.startswith("wrap_") for n in names) >= 2
    # fault pins: scheduled preemptions/spurious wakes/aborts replay clean
    assert sum(n.startswith("fault_") for n in names) >= 4
    fault_kinds = set()
    for p in CORPUS:
        if os.path.basename(p).startswith("fault_"):
            s = load_scenario(p)
            rows = s.meta.get("faults") or []
            assert rows, p  # a fault pin must actually schedule faults
            fault_kinds |= {int(r[0]) for r in rows}
    assert fault_kinds >= {1, 2, 3}  # preempt, spurious, abort all pinned
    covered = set()
    for p in CORPUS:
        covered |= set(load_scenario(p).meta.get("expect_classes", []))
    assert {"exclusion", "conservation", "deadlock", "collision",
            "liveness"} <= covered


@pytest.mark.parametrize("path", CORPUS,
                         ids=[os.path.splitext(os.path.basename(p))[0]
                              for p in CORPUS])
def test_corpus_replay(path):
    scenario = load_scenario(path)
    expect = set(scenario.meta.get("expect_classes", []))
    problems = case_problems(scenario, modes=MODES)
    got = failure_classes(problems)
    assert got == expect, (problems[:4], expect)
