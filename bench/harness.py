"""What every cell shares: finding its files, the device, the result line.

The benchmark is driven by data. A cell is ``workloads/<cell>.json``; it
names its configuration (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``) and its runner (``runners/<runner>.py``).
A metric is ``metrics/<metric>.py``; its unit comes from ``BENCHMARK.json``
at the checkout root, and every cell reports every metric of its group. Nothing here lists
cells or metrics: adding one is adding files.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(Exception):
    """The run cannot measure here; the message says why."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise Refused(f"no such file: {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, bench: Path = BENCH) -> dict:
    """The cell's own file, with its configuration and traffic read in."""
    cell = load_json(bench / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["config_file"] = load_json(bench / "configs" / f"{cell['config']}.json")
    cell["traffic_file"] = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    return cell


def cell_metrics(benchmark: dict, trace: bool) -> list[dict]:
    """The metrics a cell reports: end to end untraced, per layer traced."""
    return benchmark["per_layer" if trace else "end_to_end"]


def read_metrics(metrics: list[dict], run: dict, bench: Path = BENCH) -> dict:
    """Each metric's reader applied to the run; a reader may find nothing."""
    out = {}
    for m in metrics:
        reader = load_module(bench / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(run)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or :class:`Refused`.

    There is no CPU fallback: a number taken on the host's CPU is not a
    measurement of this system.
    """
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise Refused(f"no TPU: JAX found {len(devices)} {platform} device(s)")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    import jax
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def use_checkout_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins. Every program is cached, so
    that only a checkout's first run of a cell compiles.
    """
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_checkout_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts executables that JAX builds or loads while ``active``.

    Before that, it sums the seconds of JAX's own timed events by name
    (tracing, lowering, compiling or loading from the cache), so that the
    set-up can be split.
    """

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        self.setup: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.active:
            self.count += event == self.EVENT
        else:
            name = event.rsplit("/", 1)[-1]
            self.setup[name] = self.setup.get(name, 0.0) + duration


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None,
                checks: list[dict]) -> str:
    """The last line of standard output; the compared numbers come last."""
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return json.dumps(line)


def print_checks(checks: list[dict]) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for c in checks:
        print(f"check {c['name']} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
