"""Compiles for one TPU v5e chip, described and not attached.

Each test lowers and compiles a program of the main paths for a v5e with
the TPU compiler installed here.  Nothing runs, so this says nothing about
results or times; it catches what only the chip's compiler refuses: block
shapes off the (8, 128) tiling, dtypes Mosaic cannot lower, programs that
do not fit the device.  The topology is described inside a fixture, never
at import, so only the worker that runs this file loads the TPU library.

The strict xfails pin what Mosaic refuses today; a fix turns them into
XPASS failures, which is the cue to drop the mark.
"""

import functools
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src.pallas.mosaic.error_handling import MosaicError
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.rglru.kernel import rglru_scan_pallas
from repro.kernels.ticket_dispatch.kernel import ticket_dispatch_pallas
from repro.models.model import decode_step, forward, init_cache, init_params
from repro.sim import engine, isa
from repro.sim.engine_pallas import make_run_pallas
from repro.sim.programs import PROG_LEN

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import fig3_mutexbench  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e chip, with the persistent compile cache off: entries compiled
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fig3_shape():
    """fig3's padded sweep shape: (cells, threads, memory words)."""
    spec = fig3_mutexbench.spec()
    cells = spec.cells()
    return (len(cells), max(c.n_threads for c in cells),
            max(spec.layout_for(c).mem_words for c in cells))


def _sweep_args(sharding, n_cells, n_threads, mem_words):
    s = functools.partial(_sds, sharding)
    return (s((n_cells, PROG_LEN, 5)), s((n_cells, n_threads)),
            s((n_cells, n_threads, isa.N_REGS)), s((n_cells, mem_words)),
            s((n_cells,)), s((n_cells,), jnp.uint32), s((n_cells,)),
            s((n_cells,)), s((n_cells, 9)), s((n_cells,)), s((n_cells,)),
            s((n_cells,)))


def _drivers(n_threads, mem_words):
    return {
        "map": lambda: engine._make_run_map(n_threads, mem_words, 1),
        "vmap": lambda: engine._make_run_batched(n_threads, mem_words, 1),
        "sched": lambda: engine._make_run_sched(
            n_threads, mem_words, 1, engine.DEFAULT_LANES,
            engine.DEFAULT_CHUNK),
        "pallas": lambda: make_run_pallas(n_threads, mem_words, 1, PROG_LEN,
                                          128, interpret=False),
    }


@pytest.mark.parametrize("mode", [
    "map", "vmap", "sched",
    pytest.param("pallas", marks=pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason="Mosaic: the (1, n_threads) and (1,) blocks break the "
               "(8, 128) block-shape rule; past that, the int32 argmin in "
               "engine._step: 'Only float32 is supported'")),
])
def test_sweep_driver_compiles_at_fig3_shape(one_chip, mode):
    n_cells, n_threads, mem_words = _fig3_shape()
    assert (n_cells, n_threads) == (273, 64)
    fn = _drivers(n_threads, mem_words)[mode]()
    compiled = jax.jit(fn).lower(
        *_sweep_args(one_chip, n_cells, n_threads, mem_words)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def _granite_shapes(one_chip):
    cfg = get_config("granite-moe-1b-a400m")
    place = functools.partial(jax.tree.map, lambda s: _sds(
        one_chip, s.shape, s.dtype))
    params = place(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    return cfg, params


def test_granite_prefill_compiles_at_published_width(one_chip):
    """The ServeEngine prefill for one 16-token prompt."""
    cfg, params = _granite_shapes(one_chip)
    assert cfg.d_model == 1024 and cfg.vocab == 49155

    def prefill(p, tokens, last):
        logits, _, cache = forward(p, {"tokens": tokens}, cfg,
                                   collect_cache=True)
        return logits[0, last], cache

    compiled = jax.jit(prefill).lower(
        params, _sds(one_chip, (1, 16)), _sds(one_chip, ())).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 2 << 30


def test_granite_decode_compiles_at_published_width(one_chip):
    """The ServeEngine decode step: 4 lanes, a 256-token context."""
    cfg, params = _granite_shapes(one_chip)
    cache = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         jax.eval_shape(lambda: init_cache(cfg, 4, 256)))
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, _sds(one_chip, (4, 1)),
                          _sds(one_chip, (4,))).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 2 << 30


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_compiles(one_chip):
    fn = functools.partial(rglru_scan_pallas, interpret=False)
    a = _sds(one_chip, (2048, 4096), jnp.float32)
    _assert_kernel(jax.jit(fn).lower(a, a).compile())


@pytest.mark.parametrize("dtype", [
    jnp.float32,
    pytest.param(jnp.bfloat16, marks=pytest.mark.xfail(
        strict=True, raises=MosaicError,
        reason="Mosaic: 'cannot statically prove that index in dimension 0 "
               "is a multiple of 8' for the one-row bf16 slices")),
], ids=["float32", "bfloat16"])
def test_mamba_scan_compiles(one_chip, dtype):
    """falcon-mamba-7b widths: d_inner 8192, d_state 16, 2048 tokens."""
    L, D, N = 2048, 8192, 16
    s = functools.partial(_sds, one_chip)
    fn = functools.partial(selective_scan_pallas, interpret=False)
    _assert_kernel(jax.jit(fn).lower(
        s((L, D), dtype), s((L, D), dtype), s((D, N), dtype),
        s((L, N), dtype), s((L, N), dtype), s((D,), dtype)).compile())


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="Mosaic: 'Shape mismatch in input, indices and "
                          "output' in the one-hot ticket kernel")
def test_ticket_dispatch_compiles(one_chip):
    fn = functools.partial(ticket_dispatch_pallas, n_experts=32,
                           interpret=False)
    _assert_kernel(jax.jit(fn).lower(_sds(one_chip, (4096, 8))).compile())
