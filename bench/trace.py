"""From a JAX profiler trace to the per-layer numbers of a traced window.

The window is the benchmark's own host spans: ``bench.run_sweep`` around
each timed sweep, from the first span's start to the last span's end.

Device busy time comes from the TPU runtime's own events in the trace:
each program execution runs from ``tpu::System::Execute`` (the launch) to
``tpu::System::Execute=>Done`` (the completion the runtime observes). The
device planes' per-operation lines (``XLA Ops``) cannot carry it: the
profiler records one event per operation per ``while_loop`` iteration, its
device buffer holds about 6.3 million of them, and a grid sweep makes tens
of millions, so the device lines stop a second or two into the first sweep.
They still name the operations that take the device's time, and the
breakdown reads them as far as they go.

* busy: the union of the executions inside the window;
* per sweep, the longest execution inside its span, which is the sweep
  driver's ``while_loop`` program, and the span's time outside executions;
* the breakdown: the device operations that took most time as far as the
  device lines go, and the longest idle gaps inside the window, each named
  by the innermost host event running at its midpoint.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path

SWEEP_SPAN = "bench.run_sweep"
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
TOP = 10


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {len(files)}")
    return files[0]


def extract(xplane: Path) -> dict:
    """Host events as ``(start, end, name)`` in ns, and the device's time per
    operation name with the number of device events read."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(xplane))
    host, op_ns, n_device = [], defaultdict(float), 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    op_ns[e.name] += e.duration_ns
                    n_device += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.end_ns, e.name) for e in line.events]
    return {"host": host, "op_ns": dict(op_ns), "device_events": n_device}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same points."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def executions(host: list[tuple]) -> list[tuple[float, float]]:
    """Each launch paired with the first completion after it, in order."""
    launches = sorted(s for s, _, n in host if n == LAUNCH)
    dones = sorted(s for s, _, n in host if n == DONE)
    out, j = [], 0
    for s in launches:
        while j < len(dones) and dones[j] < s:
            j += 1
        if j == len(dones):
            break
        out.append((s, dones[j]))
        j += 1
    return out


def reduce(events: dict, sweep_events: list[int]) -> dict:
    """The traced window's numbers; ``sweep_events`` are the exact simulated
    events of each traced sweep, in order."""
    host = events["host"]
    spans = sorted((s, e) for s, e, n in host if n == SWEEP_SPAN)
    if len(spans) != len(sweep_events):
        raise ValueError(f"{len(spans)} {SWEEP_SPAN} spans in the trace for "
                         f"{len(sweep_events)} sweeps")
    lo, hi = spans[0][0], spans[-1][1]
    runs = executions(host)
    busy = union(clip(runs, lo, hi))
    if not busy:
        raise ValueError("no program execution inside the traced window")

    driver_ns, host_ms = 0.0, []
    for a, b in spans:
        inside = [e - s for s, e in clip(runs, a, b)]
        if not inside:
            raise ValueError("a traced sweep ran no program")
        driver_ns += max(inside)
        host_ms.append((b - a - sum(inside)) * 1e-6)

    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    ops = sorted(events["op_ns"].items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(e - s for s, e in busy) * 1e-9,
        "events": sum(sweep_events),
        "driver_ns": driver_ns,
        "host_ms_per_sweep": host_ms,
        "device_events": events["device_events"],
        "breakdown": {
            "device_ops": [[name, ns * 1e-9] for name, ns in ops],
            "idle_gaps": [[host_activity(host, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps[:TOP]],
        },
    }


def host_activity(host: list[tuple], at: float) -> str:
    """The innermost host event running at ``at``, or ``idle host``."""
    running = [(e - s, n) for s, e, n in host if s <= at < e]
    return min(running)[1] if running else "idle host"


def reduce_dir(trace_dir: Path, sweep_events: list[int]) -> dict:
    return reduce(extract(find_xplane(trace_dir)), sweep_events)
