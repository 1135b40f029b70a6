"""The reduction from trace events to the per-layer numbers."""

import pytest

import harness
from conftest import BENCH

trace = harness.load_module(BENCH / "trace.py", "t_trace")

L, D = trace.LAUNCH, trace.DONE
HOST = [(0, 100, "bench.run_sweep"), (0, 20, "build"), (90, 100, "readback"),
        (150, 300, "bench.run_sweep"), (150, 170, "build"),
        (20, 21, L), (90, 91, D), (170, 171, L), (280, 281, D),
        (285, 286, L), (290, 291, D)]
OPS = {"fusion.2": 40.0, "fusion.1": 140.0}


def events(host=HOST):
    return {"host": host, "op_ns": OPS, "device_events": 3}


def test_reduction_of_a_known_window():
    out = trace.reduce(events(), [1000, 2000])
    assert out["window_s"] == pytest.approx(300e-9)
    assert out["busy_s"] == pytest.approx(185e-9)           # 70 + 110 + 5
    assert out["driver_ns"] == pytest.approx(180)           # longest per sweep
    assert out["events"] == 3000
    assert out["host_ms_per_sweep"] == pytest.approx([30e-6, 35e-6])
    assert out["breakdown"]["device_ops"] == [["fusion.1", pytest.approx(140e-9)],
                                              ["fusion.2", pytest.approx(40e-9)]]
    assert out["breakdown"]["idle_gaps"] == [
        ["idle host", pytest.approx(80e-9)], ["build", pytest.approx(20e-9)],
        ["bench.run_sweep", pytest.approx(10e-9)],
        ["bench.run_sweep", pytest.approx(5e-9)]]


def test_launches_pair_with_the_next_completion():
    host = [(5, 6, L), (9, 10, D), (10, 11, L), (12, 13, L), (30, 31, D),
            (40, 41, D)]
    assert trace.executions(host) == [(5, 9), (10, 30), (12, 40)]


def test_a_window_with_no_execution_is_an_error():
    host = [h for h in HOST if h[2] not in (L, D)]
    with pytest.raises(ValueError, match="no program execution"):
        trace.reduce(events(host), [1, 2])


def test_every_sweep_needs_its_span():
    with pytest.raises(ValueError, match="spans"):
        trace.reduce(events(), [1])


RECORDED = BENCH / "tests" / "data" / "tpu_v5e_tiny_sweeps.xplane.pb"


def test_reduction_of_a_recorded_tpu_trace():
    """Three traced sweeps of a small cell (ticket and twa at 1 and 4
    threads, horizon 400, 2 seeds; 272, 315 and 272 events) on one TPU v5e,
    cut from a longer trace to those sweeps' spans."""
    events = trace.extract(RECORDED)
    assert events["device_events"] == 27960
    assert len(trace.executions(events["host"])) == 3
    out = trace.reduce(events, [272, 315, 272])
    assert out["window_s"] == pytest.approx(0.043168985)
    assert out["busy_s"] == pytest.approx(0.01101577)
    assert out["driver_ns"] == pytest.approx(11015770)
    assert out["host_ms_per_sweep"] == pytest.approx([11.502378, 10.393408, 9.964468])
    assert out["breakdown"]["idle_gaps"][0] == ["$workloads.py:166 run_sweep",
                                                pytest.approx(0.011240818)]
    assert out["breakdown"]["device_ops"][0][0].startswith("%while.1 = ")
