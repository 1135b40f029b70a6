"""Drive one run of the benchmark on the CPU, with the chip check stubbed.

    python bench/tests/drive.py <bench dir> [--fault NAME] -- <run.py args>

For the harness's own tests only: the stub lets the CPU stand in for the
TPU, with ``run_sweep`` picking its driver as it does on a TPU, and
``--fault`` breaks the timed path underneath the runner, to show that the
comparison with the reference then reads ``correct: false``. Faults:

* ``frozen_step``: the lockVM's step leaves the simulation as it was,
  counting the event only, so that the loop still ends at the event cap;
* ``half_batch``: the engine runs the first half of the sweep's cells
  only, and the rest get the mean of those;
* ``altered_answer``: each cell's first thread reports one acquisition
  more than it made;
* ``one_lane``: the same, in the last cell of each sweep only, which under
  ``vmap`` is the last lane.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def break_program(fault: str) -> None:
    import numpy as np

    from repro.sim import engine

    if fault == "frozen_step":
        engine._step = lambda c, s: s._replace(events=s.events + 1)
        engine._build_engine.cache_clear()
        return
    run_sweep = engine.run_sweep

    def broken(programs, **kw):
        if fault in ("altered_answer", "one_lane"):
            out = run_sweep(programs, **kw)
            rows = slice(None) if fault == "altered_answer" else slice(-1, None)
            out["acquisitions"] = out["acquisitions"].copy()
            out["acquisitions"][rows, 0] += 1
            return out
        assert fault == "half_batch", fault
        n = len(programs)
        half = max(n // 2, 1)
        cut = {k: (v[:half] if np.ndim(v) and len(v) == n else v)
               for k, v in kw.items() if k != "faults"}
        if kw.get("faults") is not None:
            cut["faults"] = tuple(a[:half] for a in kw["faults"])
        out = run_sweep(programs[:half], **cut)
        for k, v in out.items():
            if isinstance(v, np.ndarray) and v.shape[:1] == (half,):
                mean = v.astype(np.float64).mean(axis=0).astype(v.dtype)
                out[k] = np.concatenate([v, np.repeat(mean[None], n - half,
                                                      axis=0)])
        return out

    engine.run_sweep = broken


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("bench")
    p.add_argument("--fault")
    args, run_args = p.parse_known_args()
    run_args = [a for a in run_args if a != "--"]
    sys.path.insert(0, str(REPO / "src"))
    sys.path.insert(0, args.bench)
    import harness
    import run

    harness.use_checkout_cache = lambda: None
    from repro.sim import engine
    choose_mode = engine.choose_mode
    engine.choose_mode = lambda backend, **kw: choose_mode("tpu", **kw)
    if args.fault:
        break_program(args.fault)

    def cpu_devices(chips):
        import jax
        return jax.devices()[:chips]

    return run.main(run_args, require=cpu_devices)


if __name__ == "__main__":
    sys.exit(main())
