#!/usr/bin/env python3
"""One-chip smoke run of the lockVM sweep and of full-width serving.

    python3 chip_smoke.py

Runs in one process on one TPU, through the entry points a user calls:

  (a) device: platform, device kind and device count;
  (b) fig3: the MutexBench grid (13 locks x 7 thread counts x 3 seeds) as
      one ``run_sweep(SweepSpec(...))`` with ``mode="auto"``;
  (c) drivers: ``map``, ``vmap`` and ``sched`` agree bit for bit on
      ``bench_engine.SMOKE_CELLS``;
  (d) corpus: ``tests/corpus/*.npz`` replayed through those drivers agrees
      with the sequential NumPy oracle, and each entry reports exactly its
      pinned invariant classes;
  (e) serve: ``repro.launch.serve.main`` for granite-moe-1b-a400m at its
      published widths, 8 requests on 4 lanes.

Any failed phase raises and exits non-zero.  Without a TPU it exits
non-zero before any phase: there is no CPU fallback.  The last line of
stdout is ``{"ok": true, "device": {...}}``.  The seconds it prints are
one unbenchmarked run each, not measurements.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

CHIP_DRIVERS = ("map", "vmap", "sched")
SERVE_ARGV = ("--arch", "granite-moe-1b-a400m", "--requests", "8",
              "--lanes", "4", "--max-ctx", "256", "--max-new", "16")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_info() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def phase_fig3(**spec_kw) -> dict:
    """(b) The fig3 grid through ``run_sweep`` with ``mode="auto"``, at
    fig3's own horizon unless ``spec_kw`` says otherwise.

    A warm-up at horizon 1 has the same padded shapes and resolves to the
    same driver, so it pays the host build and the compile and runs almost
    no events; the timed sweep after it pays no compile.
    """
    from benchmarks import fig3_mutexbench as fig3
    from repro.sim.workloads import run_sweep
    spec = fig3.spec(**spec_kw)
    t0 = time.perf_counter()
    run_sweep(dataclasses.replace(spec, horizon=1))
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = run_sweep(spec)
    sweep_s = time.perf_counter() - t0
    mode = results[0]["mode"]
    assert mode in CHIP_DRIVERS, mode
    assert len(results) == len(spec.cells()), len(results)
    for r in results:
        assert r["events"] > 0 and r["throughput"] > 0, (
            r["lock"], r["n_threads"], r["seed"])
    out = {"driver": mode, "cells": len(results),
           "sum_events": results[0]["pad_stats"]["sum_events"],
           "setup_s": setup_s, "sweep_s": sweep_s}
    log(f"fig3: driver={mode} cells={out['cells']} "
        f"sum_events={out['sum_events']} horizon={spec.horizon}")
    log(f"fig3: one chip run, unbenchmarked: build+compile warm-up "
        f"{setup_s:.1f} s, sweep {sweep_s:.1f} s")
    return out


def phase_drivers(cells=None) -> dict:
    """(c) ``map``/``vmap``/``sched`` agree bit for bit on one sweep."""
    from benchmarks.bench_engine import SMOKE_CELLS
    from repro.sim import engine
    from repro.sim.workloads import pack_engine_cells
    programs, kw = pack_engine_cells(cells or SMOKE_CELLS, seeds=1,
                                     collect_latency=True)
    ref = None
    for mode in CHIP_DRIVERS:
        out = engine.run_sweep(programs, mode=mode, **kw)
        ref = out if ref is None else ref
        for key in engine.OUT_KEYS:
            assert np.array_equal(ref[key], out[key]), (mode, key)
    log(f"drivers: {'/'.join(CHIP_DRIVERS)} bit-identical on "
        f"{len(programs)} cells, sum_events={int(ref['events'].sum())}")
    return {"cells": len(programs), "sum_events": int(ref["events"].sum())}


def phase_corpus(paths=None) -> dict:
    """(d) Corpus replay through the chip drivers against the host oracle."""
    from repro.sim.check import failure_classes, load_scenario, replay_corpus
    paths = paths or sorted(glob.glob(os.path.join(ROOT, "tests", "corpus",
                                                   "*.npz")))
    assert paths, "no corpus entries"
    problems = replay_corpus(paths, modes=CHIP_DRIVERS, batch_oracle=False)
    bad, flagged = [], 0
    for path, probs in zip(paths, problems):
        expect = set(load_scenario(path).meta.get("expect_classes", []))
        got = failure_classes(probs)
        flagged += bool(expect)
        if got != expect or "differential" in got:
            bad.append((os.path.basename(path), sorted(got), sorted(expect),
                        probs[:2]))
    assert not bad, bad
    log(f"corpus: {len(paths)} entries replayed through "
        f"{'/'.join(CHIP_DRIVERS)}, no differential against the oracle, "
        f"{flagged} broken-lock entries flag exactly their pinned classes")
    return {"entries": len(paths), "flagged": flagged}


def phase_serve(argv=SERVE_ARGV) -> dict:
    """(e) ``python -m repro.launch.serve`` in-process, then its outputs."""
    from repro.launch import serve
    from repro.models.model import forward
    t0 = time.perf_counter()
    out = serve.main(list(argv))
    serve_s = time.perf_counter() - t0
    eng, reqs = out["engine"], out["requests"]
    cfg = eng.cfg
    n_requests = int(argv[list(argv).index("--requests") + 1])
    assert len(reqs) == n_requests, len(reqs)
    for r in reqs:
        assert r.done.is_set(), r.ticket
        assert len(r.tokens_out) == r.max_new_tokens, (r.ticket,
                                                       len(r.tokens_out))
        assert all(0 <= t < cfg.vocab for t in r.tokens_out), r.ticket
    # logits of every answered sequence, from the same params
    width = max(len(r.text_ids) for r in reqs)
    tokens = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        tokens[i, :len(r.text_ids)] = r.text_ids
    logits = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg)[0])(
        eng.params, tokens)
    logits = np.asarray(logits[..., :cfg.vocab], np.float32)
    assert np.isfinite(logits).all(), "non-finite logits"
    n_tokens = sum(len(r.tokens_out) for r in reqs)
    log(f"serve: {cfg.name} d_model={cfg.d_model} n_layers={cfg.n_layers} "
        f"vocab={cfg.vocab}: {len(reqs)} requests answered, {n_tokens} "
        f"tokens, ids < vocab, logits {logits.shape} finite; one chip run, "
        f"unbenchmarked: {serve_s:.1f} s with compiles")
    return {"requests": len(reqs), "tokens": n_tokens}


def main() -> int:
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"[chip_smoke] needs a TPU, found {dev}; there is no CPU "
              f"fallback", file=sys.stderr)
        return 2
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    from repro.launch.compile_cache import use_checkout_cache
    cache = use_checkout_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    log(f"compile cache: {cache} ({'warm' if warm else 'cold'})")
    t0 = time.perf_counter()
    phase_fig3()
    phase_drivers()
    phase_corpus()
    phase_serve()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
