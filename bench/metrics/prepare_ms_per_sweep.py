"""prepare_ms_per_sweep: per traced sweep, the host time before the
device starts, outside program executions, in ms: the program's
``lockvm.build``, ``lockvm.pack`` and ``lockvm.dispatch`` spans (building
the cells, padding and stacking them, the upload and the call), mean over
the sweeps (profiler trace).
"""

import spans


def read(run: dict) -> float | None:
    return spans.mean_ms(run, spans.PREPARE)
