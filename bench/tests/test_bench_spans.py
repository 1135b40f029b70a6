"""The reduction of the program's spans, and the four readers built on it."""

import shutil

import pytest

import harness
import spans
from conftest import BENCH

L, D = spans.trace.LAUNCH, spans.trace.DONE

# Two sweeps. The first marks its phases, with arguments encoded in some
# names as TraceAnnotation may; one execution runs from 30 to 80. The
# second comes from a program without spans.
HOST = [(0, 100, "bench.run_sweep", {}),
        (1, 99, "lockvm.sweep#cells=8,mode=auto#", {"cells": 8}),
        (2, 20, "lockvm.build", {}),
        (20, 25, "lockvm.pack", {}),
        (25, 35, "lockvm.dispatch#mode=sched,lanes=4#", {"mode": "sched"}),
        (30, 31, L, {}), (80, 81, D, {}),
        (35, 90, "lockvm.readback", {}),
        (90, 95, "lockvm.assemble", {"lanes": 4, "lane_steps": 2048}),
        (95, 98, "lockvm.assemble", {}),
        (150, 300, "bench.run_sweep", {}), (170, 171, L, {}), (280, 281, D, {})]
NAMED = [(s, e, spans.base_name(n), a) for s, e, n, a in HOST]


def test_spans_are_matched_by_name_and_clipped_by_executions():
    first, second = spans.reduce(NAMED)
    assert first["ms"] == pytest.approx({
        "lockvm.sweep": 48e-6, "lockvm.build": 18e-6, "lockvm.pack": 5e-6,
        "lockvm.dispatch": 5e-6, "lockvm.readback": 10e-6,
        "lockvm.assemble": 8e-6})
    assert first["lane_steps"] == 2048
    assert second == {"ms": {}, "lane_steps": None}


def test_encoded_names_lose_their_arguments():
    assert spans.base_name("lockvm.dispatch#mode=sched,lanes=4#") == \
        "lockvm.dispatch"
    assert spans.base_name("lockvm.build") == "lockvm.build"


def test_a_window_without_program_spans_has_nothing_to_read(monkeypatch):
    bare = [h for h in NAMED if not h[2].startswith(spans.PREFIX)]
    monkeypatch.setattr(spans, "load", lambda _: spans.reduce(bare))
    run = {"trace": {"events": 10, "driver_ns": 100.0}}
    assert spans.sweeps(run) is None
    assert spans.sweeps({"trace": None}) is None
    for name in NEW:
        assert reader(name).read(run) is None, name


NEW = ("lane_useful_share", "ns_per_lane_step", "prepare_ms_per_sweep",
       "drain_ms_per_sweep")


def reader(name: str):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", f"t_{name}")


@pytest.fixture
def one_sweep(monkeypatch):
    monkeypatch.setattr(spans, "load", lambda _: spans.reduce(NAMED[:10]))
    return {"trace": {"events": 512, "driver_ns": 4096.0}}


def test_the_readers_split_loop_and_host_time(one_sweep):
    share = reader("lane_useful_share").read(one_sweep)
    step = reader("ns_per_lane_step").read(one_sweep)
    assert share == pytest.approx(25.0)          # 512 of 2048 lane-steps
    assert step == pytest.approx(2.0)            # 4096 ns over 2048
    assert step * 100 / share == pytest.approx(4096.0 / 512)
    assert reader("prepare_ms_per_sweep").read(one_sweep) == \
        pytest.approx(28e-6)
    assert reader("drain_ms_per_sweep").read(one_sweep) == \
        pytest.approx(18e-6)


def test_a_sweep_without_its_counter_leaves_the_loop_split_out(monkeypatch):
    no_counter = [(s, e, n, {}) for s, e, n, _ in NAMED]
    monkeypatch.setattr(spans, "load", lambda _: spans.reduce(no_counter))
    run = {"trace": {"events": 512, "driver_ns": 4096.0}}
    assert reader("lane_useful_share").read(run) is None
    assert reader("ns_per_lane_step").read(run) is None
    assert reader("prepare_ms_per_sweep").read(run) is not None


def test_the_old_recording_predates_the_spans():
    """The recording of ``test_bench_trace.py``, made before the program
    had spans: three sweeps, none with a span, as on an older checkout."""
    old = BENCH / "tests" / "data" / "tpu_v5e_tiny_sweeps.xplane.pb"
    assert spans.reduce(spans.extract(old)) == [
        {"ms": {}, "lane_steps": None}] * 3


SPANNED = BENCH / "tests" / "data" / "tpu_v5e_tiny_sweeps_with_spans.xplane.pb"


def test_a_recorded_tpu_trace_splits_loop_and_host_time(monkeypatch, tmp_path):
    """Three traced sweeps of a small cell on one TPU v5e, with the
    program's spans: ticket and twa at 1 and 4 threads, horizon 400, 2
    seeds, on the ``vmap`` driver; 272, 272 and 320 events."""
    (tmp_path / "profile").mkdir()
    shutil.copy(SPANNED, tmp_path / "profile")
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    run = {"trace": spans.trace.reduce(spans.trace.extract(SPANNED),
                                       [272, 272, 320])}
    got = {name: reader(name).read(run)
           for name in NEW + ("loop_ns_per_event", "host_ms_per_sweep")}
    # 8 lanes step together until the longest cell ends: 63, 63, 72 steps
    assert [s["lane_steps"] for s in spans.load(tmp_path)] == [504, 504, 576]
    assert got["lane_useful_share"] == pytest.approx(100 * 864 / 1584)
    assert got["ns_per_lane_step"] * 100 / got["lane_useful_share"] == \
        pytest.approx(got["loop_ns_per_event"], rel=1e-6)
    assert got["prepare_ms_per_sweep"] == pytest.approx(6.228408333)
    assert got["drain_ms_per_sweep"] == pytest.approx(4.894161333)
    assert abs(got["prepare_ms_per_sweep"] + got["drain_ms_per_sweep"]
               - got["host_ms_per_sweep"]) < 1.0
