"""lane_useful_share: the share of the sweep driver's lane-steps that ran an
event, over the traced sweeps, in percent (program counter).

A lane-step is one step of one lane of the driver's loop: the counter in
the loop carry times the lanes stepped together (``pad_stats["lanes"]``
and ``["lane_steps"]``), which the program also gives as arguments of its
``lockvm.assemble`` span, read here from the traced window. The rest are
steps of lanes that had finished, or parked, or waited out a burst.
"""

import spans


def read(run: dict) -> float | None:
    lane_steps = spans.lane_steps(run)
    if not lane_steps:
        return None
    return 100.0 * run["trace"]["events"] / lane_steps
