"""The program's own host spans in a traced window, reduced per sweep.

The lockVM marks the host phases of a sweep with ``lockvm.*`` spans on the
profiler's clock (``jax.profiler.TraceAnnotation`` in
``repro.sim.workloads.run_sweep`` and ``repro.sim.engine.run_sweep``):
``lockvm.sweep`` around ``lockvm.build``, ``lockvm.pack``,
``lockvm.dispatch``, ``lockvm.readback``, ``lockvm.assemble`` and
``lockvm.store``. For each ``bench.run_sweep`` span of the window this
gives each span name's time outside program executions, in ms (an
execution runs from the runtime's launch to its completion, as in
``trace.py``), and the ``lane_steps`` the sweep's driver counted: the
argument of that name on ``lockvm.assemble``.

A name is matched on its part before any ``#``, where ``TraceAnnotation``
may encode its arguments. A program without these spans gives sweeps with
none, and the readers built on this module then find nothing.

The metric readers read the trace that the runner leaves in ``out/trace``
after a traced run. By hand, the mean per sweep of each span:

    python3 bench/spans.py [trace dir]
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

from harness import BENCH, load_module

PREFIX = "lockvm."
TRACE_DIR = BENCH / "out" / "trace"   # where runners/lockvm_sweep.py traces
PREPARE = ("lockvm.build", "lockvm.pack", "lockvm.dispatch")
DRAIN = ("lockvm.readback", "lockvm.assemble")

trace = load_module(BENCH / "trace.py", "bench_trace_for_spans")


def base_name(name: str) -> str:
    return name.split("#", 1)[0]


def extract(xplane: Path) -> list[tuple]:
    """The host events this reduction reads, as ``(start, end, name, args)``
    in ns: sweep spans, launches, completions and ``lockvm.*`` spans."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(xplane))
    plain = {trace.SWEEP_SPAN, trace.LAUNCH, trace.DONE}
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = base_name(e.name)
                if name in plain:
                    out.append((e.start_ns, e.end_ns, name, {}))
                elif name.startswith(PREFIX):
                    out.append((e.start_ns, e.end_ns, name, dict(e.stats)))
    return out


def reduce(host: list[tuple]) -> list[dict]:
    """Per ``bench.run_sweep`` span, in order: ``ms``, each ``lockvm.*``
    name's time outside executions summed over its spans in the sweep, and
    ``lane_steps``, the sum of that argument over them (None without one)."""
    busy = trace.union(trace.executions([(s, e, n) for s, e, n, _ in host]))
    out = []
    for a, b in sorted((s, e) for s, e, n, _ in host
                       if n == trace.SWEEP_SPAN):
        ms, lane_steps = {}, None
        for s, e, name, args in host:
            if not (name.startswith(PREFIX) and a <= s and e <= b):
                continue
            inside = sum(y - x for x, y in trace.clip(busy, s, e))
            ms[name] = ms.get(name, 0.0) + (e - s - inside) * 1e-6
            if "lane_steps" in args:
                lane_steps = (lane_steps or 0) + int(args["lane_steps"])
        out.append({"ms": ms, "lane_steps": lane_steps})
    return out


def load(trace_dir: Path) -> list[dict] | None:
    """:func:`reduce` of the one trace under ``trace_dir``, or None if
    there is none."""
    try:
        xplane = trace.find_xplane(trace_dir)
    except ValueError:
        return None
    return _reduce_file(xplane, xplane.stat().st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _reduce_file(xplane: Path, mtime_ns: int) -> list[dict]:
    """Each reader of a run asks for the same trace: it is read once."""
    return reduce(extract(xplane))


def sweeps(run: dict) -> list[dict] | None:
    """The traced run's sweeps, or None where the run was not traced or its
    program marked no span."""
    if not run.get("trace"):
        return None
    out = load(TRACE_DIR)
    if not out or not any(s["ms"] for s in out):
        return None
    return out


def lane_steps(run: dict) -> int | None:
    """The lane-steps of every traced sweep, or None where one lacks them."""
    out = sweeps(run)
    if out is None or any(s["lane_steps"] is None for s in out):
        return None
    return sum(s["lane_steps"] for s in out)


def mean_ms(run: dict, names: tuple[str, ...]) -> float | None:
    """The mean per traced sweep of the named spans' summed ms."""
    out = sweeps(run)
    if out is None:
        return None
    return sum(s["ms"].get(n, 0.0) for s in out for n in names) / len(out)


def summary(per_sweep: list[dict]) -> dict:
    """Each span's mean ms per sweep, and the lane-steps of each sweep."""
    names = sorted({n for s in per_sweep for n in s["ms"]})
    return {"sweeps": len(per_sweep),
            "ms_per_sweep": {n: sum(s["ms"].get(n, 0.0) for s in per_sweep)
                             / len(per_sweep) for n in names},
            "lane_steps": [s["lane_steps"] for s in per_sweep]}


if __name__ == "__main__":
    found = load(Path(sys.argv[1]) if len(sys.argv) > 1 else TRACE_DIR)
    if not found:
        sys.exit("spans: no trace, or no sweep span in it")
    print("spans " + json.dumps(summary(found)))
