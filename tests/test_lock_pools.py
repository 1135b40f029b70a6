"""Pools of locks: threads that pick a random lock every iteration, with one
waiting array shared by every lock or a private array per lock (the TWA
paper's Figure 2).

Every driver and both memory forms of the step give the same statistics
for a pool, bit for bit. ``engine.memory_form`` names the form the step
takes for a sweep's memory on a backend, and the ``lockvm.dispatch`` span
carries it beside the pool's ``n_locks``, so that a trace tells index-form
time from mask-form time.
"""

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.sim import SweepSpec, engine, run_sweep
from repro.sim.programs import Layout

POOLS = (1, 2, 4)
# the shared arm and the private arm in every sweep; 2 and 5 threads so
# that a sweep pads threads and memory as the figure's sweeps do
ARMS = (False, True)
VARIANTS = ("vmap", "sched", "vmap-mask", "sched-mask")
RESULT_KEYS = ("acquisitions", "waited_acquisitions", "handover_sum",
               "handover_count", "events", "sleeping", "mem", "throughput",
               "avg_handover", "n_locks", "private_arrays")


def pool_spec(pool: int) -> SweepSpec:
    return SweepSpec(locks="twa", threads=(2, 5), seeds=(7, 2**31 + 9),
                     cs_work=50, ncs_max=100, private_arrays=ARMS,
                     n_locks=pool, horizon=6_000)


def _tpu_form(*args, tpu, default):
    """``jax.lax.platform_dependent`` as a TPU lowering resolves it."""
    return tpu(*args)


@pytest.fixture(scope="module")
def pool_sweeps() -> dict:
    """Each pool under ``map`` (the reference here), ``vmap`` and ``sched``
    with memory by index, as on the CPU, and under ``vmap`` and ``sched``
    with the TPU's form forced, which for these sizes is by mask."""
    out = {}
    for pool in POOLS:
        spec = pool_spec(pool)
        out[pool, "map"] = run_sweep(spec, mode="map")
        out[pool, "vmap"] = run_sweep(spec, mode="vmap")
        out[pool, "sched"] = run_sweep(spec, mode="sched", lanes=3, chunk=64)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, "platform_dependent", _tpu_form)
        engine._build_engine.cache_clear()
        try:
            for pool in POOLS:
                spec = pool_spec(pool)
                out[pool, "vmap-mask"] = run_sweep(spec, mode="vmap")
                out[pool, "sched-mask"] = run_sweep(spec, mode="sched",
                                                    lanes=3, chunk=64)
        finally:
            engine._build_engine.cache_clear()
    return out


def _same(a, b) -> bool:
    if isinstance(a, float) and np.isnan(a):
        return np.isnan(b)
    return np.array_equal(a, b)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("pool", POOLS)
def test_a_pool_gives_the_same_statistics_under_every_driver_and_form(
        pool_sweeps, pool, variant):
    ref, got = pool_sweeps[pool, "map"], pool_sweeps[pool, variant]
    assert len(ref) == len(got) == 2 * 2 * len(ARMS)
    for r, g in zip(ref, got):
        assert (r["n_threads"], r["seed"], r["private_arrays"]) == (
            g["n_threads"], g["seed"], g["private_arrays"])
        for key in RESULT_KEYS:
            assert _same(g[key], r[key]), (pool, variant, r["n_threads"],
                                           r["private_arrays"], key)


def test_each_cell_reports_its_own_memory(pool_sweeps):
    """The private arm holds an array per lock; a row's ``mem`` is the
    cell's own memory, not the sweep's padded width."""
    for pool in POOLS:
        for r in pool_sweeps[pool, "map"]:
            layout = Layout(n_threads=r["n_threads"], n_locks=pool,
                            private_arrays=r["private_arrays"])
            assert len(r["mem"]) == layout.mem_words


def test_private_arrays_take_the_notifies_off_the_shared_array(pool_sweeps):
    """At 4 locks the shared arm notifies into its one array, and the
    private arm into the array of the lock, so locks 1 to 3 notify past
    the first array."""
    rows = pool_sweeps[4, "map"]
    for r in rows:
        layout = r["layout"]
        mem = np.asarray(r["mem"])
        first = mem[layout.wa_base:layout.wa_base + layout.wa_size]
        rest = mem[layout.wa_base + layout.wa_size:]
        if r["private_arrays"]:
            assert rest.any()
        else:
            assert first.any() and not rest.size


@pytest.mark.parametrize("backend,mem_words,form", [
    ("tpu", Layout(n_threads=64, n_locks=1).mem_words, "mask"),
    ("tpu", engine.DENSE_MEM_WORDS, "mask"),
    ("tpu", engine.DENSE_MEM_WORDS + 16, "index"),
    ("tpu", Layout(n_threads=64, n_locks=8).mem_words, "mask"),
    ("tpu", Layout(n_threads=64, n_locks=8, private_arrays=True).mem_words,
     "index"),
    ("tpu", Layout(n_threads=64, n_locks=64, private_arrays=True).mem_words,
     "index"),
    ("cpu", Layout(n_threads=2, n_locks=1).mem_words, "index"),
    ("gpu", engine.DENSE_MEM_WORDS, "index"),
], ids=lambda v: str(v))
def test_memory_form_is_mask_on_a_tpu_up_to_the_dense_size(backend,
                                                           mem_words, form):
    assert engine.memory_form(mem_words, backend) == form


@pytest.mark.parametrize("form", ["mask", "index"])
def test_the_step_takes_the_form_the_helper_names(monkeypatch, form):
    """Lowered for a TPU at Figure 2's pool of 8 with shared arrays (8 704
    words), the step indexes memory exactly where ``memory_form`` says
    ``index``."""
    from test_step_index_free import _index_ops
    mem_words = Layout(n_threads=64, n_locks=8).mem_words
    monkeypatch.setattr(engine, "memory_form", lambda words, backend: form)
    ops = _index_ops(False, mem_words, "tpu")
    assert ops == ({} if form == "mask" else {"gather": 3, "scatter": 3}), ops


def _dispatch_args(trace_dir) -> list[dict]:
    (xplane,) = Path(trace_dir).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(xplane))
    return [dict(e.stats) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.split("#")[0] == "lockvm.dispatch"]


def test_the_dispatch_span_names_the_pool_and_the_memory_form(tmp_path):
    spec = pool_spec(4)
    rows = run_sweep(spec, mode="vmap")                 # compile untraced
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        run_sweep(spec, mode="vmap")
    finally:
        jax.profiler.stop_trace()
    (args,) = _dispatch_args(tmp_path / "trace")
    mem_words = max(r["layout"].mem_words for r in rows)
    assert args["n_locks"] == 4
    assert args["mem_words"] == mem_words
    assert args["mem_form"] == engine.memory_form(mem_words,
                                                  jax.default_backend())
    assert args["mem_form"] == "index"                  # on the CPU
    assert args["queue"] == "none"                      # no queue in vmap
