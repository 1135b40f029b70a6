"""drain_ms_per_sweep: per traced sweep, the host time after the device
stops, outside program executions, in ms: the program's
``lockvm.readback`` and ``lockvm.assemble`` spans (copying the outputs
back, the per-cell dicts, the percentiles), mean over the sweeps
(profiler trace). With ``prepare_ms_per_sweep`` it splits
``host_ms_per_sweep``.
"""

import spans


def read(run: dict) -> float | None:
    return spans.mean_ms(run, spans.DRAIN)
