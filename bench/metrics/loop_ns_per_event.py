"""loop_ns_per_event: device time of the sweep driver's program in the
traced sweeps over the simulated events they ran, in ns per event
(profiler trace; the events are the program's exact counts)."""


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace or not trace["driver_ns"] or not trace["events"]:
        return None
    return trace["driver_ns"] / trace["events"]
