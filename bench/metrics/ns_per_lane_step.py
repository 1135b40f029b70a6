"""ns_per_lane_step: device time of the sweep driver's program in the
traced sweeps over the lane-steps its loop ran, in ns per lane-step
(profiler trace; the lane-steps are the program's own counter).

With ``lane_useful_share`` it splits ``loop_ns_per_event``, from the same
device time and events: ``loop_ns_per_event = ns_per_lane_step * 100 /
lane_useful_share``.
"""

import spans


def read(run: dict) -> float | None:
    lane_steps = spans.lane_steps(run)
    if not lane_steps:
        return None
    return run["trace"]["driver_ns"] / lane_steps
