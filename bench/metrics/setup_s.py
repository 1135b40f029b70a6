"""setup_s: seconds from process start to the start of the first timed sweep.

Covers JAX and TPU start-up, the sweep's program build, compilation or the
compile cache's load, and the warm-up sweep (host clock).
"""


def read(run: dict) -> float:
    return run["setup_s"]
