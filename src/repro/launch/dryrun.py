import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ The two lines above MUST stay first - jax locks the device count on first
# init, and the dry-run (and only the dry-run) needs 512 placeholder devices
# for the production meshes.  Smoke tests and benches see 1 device.
#
# Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.
# Per cell this produces (written to experiments/dryrun/):
#   <cell>.json     - memory_analysis, cost_analysis, timing, per-arch config
#   <cell>.hlo.txt  - compiled HLO (post-SPMD) for the roofline parser

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, applicable, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models.model import (decode_step, init_params, param_specs,
                                prefill)
from repro.optim import AdamW
from repro.train.sharding import (DEFAULT_RULES, batch_spec, tree_specs)
from repro.train.train_step import (TrainOptions, TrainState,
                                    build_train_step)

from jax.sharding import PartitionSpec as P

OUT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")

# Per-arch training options (memory fits on 16 GB v5e; see DESIGN.md §6)
ACCUM = {"mistral-large-123b": 16, "qwen2-vl-72b": 8, "grok-1-314b": 16,
         "gemma2-27b": 4, "falcon-mamba-7b": 4, "recurrentgemma-9b": 4,
         "deepseek-7b": 4, "gemma3-1b": 4, "granite-moe-1b-a400m": 2,
         "hubert-xlarge": 2}
MOMENT_DTYPE = {"grok-1-314b": "bfloat16"}
ACCUM_DTYPE = {"grok-1-314b": "bfloat16", "mistral-large-123b": "bfloat16"}


def _abstract_state(cfg, optimizer):
    def mk(key):
        params = init_params(cfg, key)
        return TrainState(params=params, opt=optimizer.init(params))
    return jax.eval_shape(mk, jax.random.PRNGKey(0))


def _state_pspecs(cfg, state_sds, mesh, rules=None):
    pspec = param_specs(cfg)
    params_specs = tree_specs(pspec, state_sds.params, mesh, rules)
    mv_specs = params_specs
    return TrainState(
        params=params_specs,
        opt=type(state_sds.opt)(step=P(), m=mv_specs, v=mv_specs),
    )


def _batch_pspecs(batch_sds, mesh, axes=None):
    from repro.train.sharding import batch_axes
    bx = batch_axes(mesh, axes)

    def one(name, sds_leaf):
        if name == "positions":  # (3, B, S): batch is dim 1
            return P(None, bx)
        return batch_spec(mesh, sds_leaf.ndim, axes=axes)
    return {k: one(k, v) for k, v in batch_sds.items()}


def _cache_pspecs(cfg, cache_sds, mesh):
    """KV/SSM cache sharding: batch -> (pod, data); kv_heads -> model when
    divisible, else the context length ('seq') shards over model — the
    32k/500k caches only fit HBM with 2-D sharding.  Leading dim is the
    period stack."""
    axis_map = {
        "k": ("layers", "batch", "seq", "kv_heads", "head_dim"),
        "v": ("layers", "batch", "seq", "kv_heads", "head_dim"),
        "ssm": ("layers", "batch", "inner", "state"),
        "conv": None,  # resolved per family below
        "h": ("layers", "batch", "lru"),
    }
    rules = dict(DEFAULT_RULES)
    rules["batch"] = ("pod", "data")
    rules["state"] = None
    model_size = mesh.shape.get("model", 1)
    if cfg.n_kv_heads and cfg.n_kv_heads % model_size == 0:
        rules["seq"] = None          # shard kv_heads over model
    else:
        rules["seq"] = "model"       # shard the context dim instead
        rules["kv_heads"] = None

    from repro.train.sharding import spec_for_axes

    def map_entry(path, sds_leaf):
        name = path[-1]
        axes = axis_map.get(name)
        if name == "conv":
            third = "inner" if cfg.family == "ssm" else "lru"
            axes = ("layers", "batch", None, third)
        if axes is None:
            return P()
        axes = axes[:sds_leaf.ndim]
        # tail (unstacked) entries lack the leading layers dim
        if sds_leaf.ndim == len(axes) - 1:
            axes = axes[1:]
        return spec_for_axes(axes[-sds_leaf.ndim:], sds_leaf.shape, mesh, rules)

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, path + (str(i),))
                              for i, v in enumerate(tree))
        return map_entry(path, tree)

    return walk(cache_sds)


def _collect(compiled, lowered, t_lower, t_compile) -> dict:
    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    mem["total_per_device"] = (mem["argument_size_in_bytes"]
                               + mem["temp_size_in_bytes"])
    try:
        ca = compiled.cost_analysis() or {}
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes accessed" in k)}
    except Exception:
        cost = {}
    return {"memory": mem, "cost_analysis": cost,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             rules: dict | None = None, accum: int | None = None,
             save_hlo: bool = True, out_dir: str = OUT_DIR,
             tag: str = "", cfg_overrides: dict | None = None,
             constrain_grads: bool = False) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    cell = SHAPES[shape_name]
    ok, reason = applicable(cfg, cell)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    if not ok:
        return {"cell": cell_id, "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    optimizer = AdamW(moment_dtype=MOMENT_DTYPE.get(arch, "float32"))
    result = {"cell": cell_id, "arch": arch, "shape": shape_name,
              "mesh": list(mesh.shape.values()),
              "params": cfg.param_count(),
              "active_params": cfg.active_param_count()}

    # hillclimb overrides: '__batch__' remaps the activation/data batch axes;
    # '__seq__' turns on Megatron-style sequence-parallel layer boundaries
    batch_ax = seq_ax = None
    if rules:
        rules = dict(rules)
        batch_ax = rules.pop("__batch__", None)
        seq_ax = rules.pop("__seq__", None)
        result["rules"] = {str(k): str(v) for k, v in rules.items()}
        if batch_ax:
            result["rules"]["__batch__"] = str(batch_ax)
        if seq_ax:
            result["rules"]["__seq__"] = str(seq_ax)

    import contextlib
    from repro.models.shard_utils import act_batch_axes, act_seq_axes, use_mesh
    ctx = act_batch_axes(batch_ax) if batch_ax else contextlib.nullcontext()
    ctx2 = act_seq_axes(seq_ax) if seq_ax else contextlib.nullcontext()

    t0 = time.time()
    with use_mesh(mesh), ctx, ctx2:
        if cell.kind == "train":
            A = accum if accum is not None else ACCUM.get(arch, 1)
            opts = TrainOptions(accum_steps=A,
                                accum_dtype=ACCUM_DTYPE.get(arch, "float32"),
                                rules=rules,
                                constrain_grads=constrain_grads)
            step = build_train_step(cfg, optimizer, opts)
            state_sds = _abstract_state(cfg, optimizer)
            batch_sds = input_specs(cfg, cell)
            state_ps = _state_pspecs(cfg, state_sds, mesh, rules)
            batch_ps = _batch_pspecs(batch_sds, mesh, batch_ax)
            jitted = jax.jit(step,
                             in_shardings=(state_ps, batch_ps),
                             out_shardings=(state_ps, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_sds, batch_sds)
            result["accum_steps"] = A
        elif cell.kind == "prefill":
            batch_sds = input_specs(cfg, cell)
            params_sds = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            params_ps = tree_specs(param_specs(cfg), params_sds, mesh, rules)
            batch_ps = _batch_pspecs(batch_sds, mesh, batch_ax)

            def prefill_fn(params, batch):
                return prefill(params, batch, cfg)

            jitted = jax.jit(prefill_fn,
                             in_shardings=(params_ps, batch_ps))
            lowered = jitted.lower(params_sds, batch_sds)
        else:  # decode
            spec = input_specs(cfg, cell)
            params_sds = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            params_ps = tree_specs(param_specs(cfg), params_sds, mesh, rules)
            cache_ps = _cache_pspecs(cfg, spec["cache"], mesh)
            tok_ps = batch_spec(mesh, 2,
                                shard_batch=cell.global_batch % 16 == 0)

            def serve_fn(params, cache, tokens, pos):
                return decode_step(params, cache, tokens, pos, cfg)

            jitted = jax.jit(serve_fn,
                             in_shardings=(params_ps, cache_ps, tok_ps, P()),
                             out_shardings=(None, cache_ps),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_sds, spec["cache"],
                                   spec["tokens"], spec["pos"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    result.update(_collect(compiled, lowered, t_lower, t_compile))
    result["status"] = "ok"

    os.makedirs(out_dir, exist_ok=True)
    if save_hlo:
        hlo_path = os.path.join(out_dir, cell_id + ".hlo.txt")
        with open(hlo_path, "w") as f:
            f.write(compiled.as_text())
        result["hlo_path"] = hlo_path
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    res = run_cell(arch, shape, multi_pod=mp, out_dir=args.out)
                    if res["status"] == "ok":
                        mem = res["memory"]["total_per_device"] / 2**30
                        print(f"[ok]   {label}: {mem:.2f} GiB/dev, "
                              f"compile {res['compile_s']}s", flush=True)
                    else:
                        print(f"[skip] {label}: {res['reason']}", flush=True)
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {label}: {type(e).__name__}: {e}",
                          flush=True)
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
