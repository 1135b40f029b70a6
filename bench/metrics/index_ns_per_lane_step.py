"""index_ns_per_lane_step: device time of the sweep driver's program over
the lane-steps its loop ran, in ns per lane-step, over the traced sweeps
whose ``lockvm.dispatch`` span says ``mem_form`` ``index`` (profiler trace;
the lane-steps are the program's own counter).

``ns_per_lane_step`` for the sweeps in which the step reads and writes
memory and its lines by one index per array, as it does past
``DENSE_MEM_WORDS`` words on a TPU. A window whose sweeps all take the
mask form, and a program whose dispatch span names no memory form, give
nothing.
"""

from __future__ import annotations

import spans

DISPATCH = "lockvm.dispatch"


def index_sweeps(host: list[tuple]) -> list[tuple[float, int]]:
    """``(driver ns, lane-steps)`` of each ``bench.run_sweep`` span of
    ``host`` (as :func:`spans.extract` gives it) whose every dispatch took
    the index form; the driver's time is the longest execution inside the
    span, as ``bench/trace.py`` reads it."""
    trace = spans.trace
    runs = trace.executions([(s, e, n) for s, e, n, _ in host])
    out = []
    for a, b in sorted((s, e) for s, e, n, _ in host
                       if n == trace.SWEEP_SPAN):
        inner = [(n, args) for s, e, n, args in host if a <= s and e <= b]
        forms = {str(args.get("mem_form")) for n, args in inner
                 if n == DISPATCH}
        steps = [int(args["lane_steps"]) for _, args in inner
                 if "lane_steps" in args]
        inside = [e - s for s, e in trace.clip(runs, a, b)]
        if forms == {"index"} and steps and inside:
            out.append((max(inside), sum(steps)))
    return out


def read(run: dict) -> float | None:
    if not run.get("trace"):
        return None
    try:
        xplane = spans.trace.find_xplane(spans.TRACE_DIR)
    except ValueError:
        return None
    found = index_sweeps(spans.extract(xplane))
    if not found:
        return None
    return sum(ns for ns, _ in found) / sum(steps for _, steps in found)
