"""host_ms_per_sweep: per traced sweep, the host-clock wall of the
``run_sweep`` call less the device time of the programs it ran, in ms:
building, padding and packing the cells, dispatch, readback and assembly
(host clock and profiler trace)."""


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace or not trace["host_ms_per_sweep"]:
        return None
    return sum(trace["host_ms_per_sweep"]) / len(trace["host_ms_per_sweep"])
