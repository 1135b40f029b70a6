"""sim_events_per_s: simulated events of every sweep in the window, summed
over all cells, over the window's wall time (host clock).

The window runs from the start of the first timed sweep to the end of the
last; each sweep ends when its results are on the host.
"""


def read(run: dict) -> float:
    return run["events"] / run["window_s"]
