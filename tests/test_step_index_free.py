"""The batched lockVM step indexes only memory, and only where that pays.

Under ``vmap`` an indexed read or write of lane-local state lowers to a
gather or scatter with one index per lane, which the TPU walks one lane
after another.  ``engine._step`` reads and writes every per-thread,
per-lock and register array by one-hot masks instead.  Memory and its
lines (``mem``, ``sharers``, ``dirty``) go by mask on a TPU up to
``engine.DENSE_MEM_WORDS`` words, and by one indexed read and one indexed
write each otherwise.  These tests lower ``jax.vmap(engine._step)`` for
each platform, at Figure 3's shape and at Figure 2's 64 private arrays, and
count the index operations in the StableHLO, so an edit that puts another
indexed read or update into the step fails on the CPU.  The TPU's form is
also run here, forced, against the CPU's, since only a chip runs it
otherwise.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import SIM_LOCKS, SweepSpec, engine, isa, run_sweep
from repro.sim.faults import FaultSchedule
from repro.sim.programs import PROG_LEN, Layout

LANES = 3
N_THREADS = 64                                        # both figures' widest cell
MEM_WORDS = Layout(n_threads=N_THREADS, n_locks=1).mem_words  # 6 464 words
# Figure 2's 64 locks with an array each: 284 672 words
BIG_MEM_WORDS = Layout(n_threads=N_THREADS, n_locks=64,
                       private_arrays=True).mem_words
N_FAULTS = 8
INDEX_OPS = ("gather", "scatter", "dynamic_slice", "dynamic_update_slice")


def _consts(batch: tuple, faults: bool) -> engine.SimConsts:
    def i32(*shape):
        return jax.ShapeDtypeStruct(batch + shape, jnp.int32)
    fault_fields = ({k: i32(N_FAULTS) for k in ("f_kind", "f_evt", "f_tid",
                                                 "f_arg")}
                    if faults else {})
    return engine.SimConsts(program=i32(PROG_LEN, 5), costs=i32(9),
                            wa_base=i32(), wa_mask=i32(), wa_size=i32(),
                            horizon=i32(), max_events=i32(), **fault_fields)


def _state(batch: tuple, mem_words: int = MEM_WORDS) -> engine.SimState:
    def init():
        z = jnp.zeros
        return engine._initial_state(
            N_THREADS, mem_words, 1, z(N_THREADS, jnp.int32),
            z((N_THREADS, isa.N_REGS), jnp.int32), z(mem_words, jnp.int32),
            jnp.int32(N_THREADS), jnp.uint32(1))
    s = jax.eval_shape(init)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(batch + x.shape, x.dtype), s)


def _index_ops(faults: bool, mem_words: int,
               platform: str) -> collections.Counter:
    lowered = jax.jit(jax.vmap(engine._step)).trace(
        _consts((LANES,), faults), _state((LANES,), mem_words)).lower(
            lowering_platforms=(platform,))
    return collections.Counter(re.findall(
        r'stablehlo\.(%s)"?\(' % "|".join(INDEX_OPS), lowered.as_text()))


@pytest.mark.parametrize("faults", [False, True], ids=["fault-free", "faults"])
def test_batched_step_indexes_nothing(faults):
    """On a TPU, at Figure 3's 6 464 words, the step has no index op."""
    assert MEM_WORDS <= engine.DENSE_MEM_WORDS
    ops = _index_ops(faults, MEM_WORDS, "tpu")
    assert not ops, ops


@pytest.mark.parametrize("platform,mem_words", [
    ("cpu", MEM_WORDS), ("tpu", BIG_MEM_WORDS)], ids=["cpu", "tpu-fig2"])
@pytest.mark.parametrize("faults", [False, True], ids=["fault-free", "faults"])
def test_large_memory_step_indexes_once_per_array(faults, platform,
                                                   mem_words):
    """Off the TPU, or past the dense size, ``mem``, ``sharers`` and
    ``dirty`` take one gather and one scatter each; every other array stays
    masked."""
    assert BIG_MEM_WORDS > engine.DENSE_MEM_WORDS
    ops = _index_ops(faults, mem_words, platform)
    assert ops == {"gather": 3, "scatter": 3}, ops


def test_effects_are_scalars(monkeypatch):
    """Every field the opcode switch returns is a scalar: a handler that
    returned a row would make ``vmap`` select whole rows across every
    branch."""
    seen = []
    switch = jax.lax.switch

    def spy(index, branches, *operands):
        out = switch(index, branches, *operands)
        seen.append(out)
        return out

    monkeypatch.setattr(jax.lax, "switch", spy)
    for dense in (True, False):
        jax.make_jaxpr(engine._step_in, static_argnums=2)(
            _consts((), True), _state(()), dense)
    assert len(seen) == 2
    for effects in seen:
        assert isinstance(effects, engine.Effects)
        shapes = {name: jnp.shape(v) for name, v in effects._asdict().items()}
        assert all(shape == () for shape in shapes.values()), shapes


# MutexBench's 13 locks; twa-timo's 32-slot abandonment ring cannot hold
# 33 threads.
MUTEXBENCH_LOCKS = tuple(lk for lk in SIM_LOCKS if lk != "twa-timo")


def _tpu_form(*args, tpu, default):
    """``jax.lax.platform_dependent`` as a TPU lowering resolves it."""
    return tpu(*args)


@pytest.fixture(scope="module")
def form_sweeps() -> tuple:
    """Every MutexBench lock at 3 and 33 threads, with faults and latency
    histograms: the CPU's form under ``map``, and the TPU's form, forced
    here, under ``vmap`` and ``sched``."""
    spec = SweepSpec(locks=MUTEXBENCH_LOCKS, threads=(3, 33), seeds=1,
                     horizon=8_000, collect_latency=True, preempt_faults=2,
                     spurious_faults=1, abort_faults=1, preempt_cost=300,
                     fault_evt_span=400)
    ref = run_sweep(spec, mode="map")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, "platform_dependent", _tpu_form)
        engine._build_engine.cache_clear()
        try:
            forced = [run_sweep(spec, mode="vmap"),
                      run_sweep(spec, mode="sched", lanes=3)]
        finally:
            engine._build_engine.cache_clear()
    return ref, forced


def _same(a, b) -> bool:
    if isinstance(a, FaultSchedule):
        return a.to_lists() == b.to_lists()
    if isinstance(a, float) and np.isnan(a):
        return np.isnan(b)
    return np.array_equal(a, b)


@pytest.mark.parametrize("lock", MUTEXBENCH_LOCKS)
def test_tpu_form_matches_cpu_form(lock, form_sweeps):
    """Memory read and written by mask gives every result key of memory
    read and written by index, cell by cell."""
    ref, forced = form_sweeps
    rows = [i for i, r in enumerate(ref) if r["lock"] == lock]
    assert len(rows) == 2
    for run in forced:
        for i in rows:
            for key in ref[i].keys() - {"mode", "pad_stats"}:
                assert _same(run[i][key], ref[i][key]), \
                    (run[i]["mode"], lock, ref[i]["n_threads"], key)
