"""Plumbing shared by every cell: where the files are, the device gate,
the table of peaks, the compile cache and compile counting, percentiles,
and the per-layer metric readers found by name."""
from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
from typing import Any, Dict, List, Optional, Sequence

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The cell cannot be run as described: a missing file, an unknown
    device kind, a device that is not the one the cell asks for."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """Import a file by path; names under ``bench/`` may hold dots."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path


def _applies(metric: Dict[str, Any], cell: str, e2e: Sequence[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric without a list is read wherever its end-to-end
    # metric is reported
    return metric.get("moves") is None or metric["moves"] in e2e


def load_cell(name: str, root: pathlib.Path = ROOT,
              benchmark: Optional[Dict[str, Any]] = None) -> Cell:
    bm = benchmark if benchmark is not None else load_json(
        root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    entry = configs[w["config"]]
    config = load_json(root / entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bm["end_to_end"] if _applies(m, name, ())]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bm["per_layer"] if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, root=root)


# -- device ------------------------------------------------------------------

def use_compile_cache(root: pathlib.Path) -> str:
    """Give JAX's persistent compilation cache a fixed directory inside the
    checkout, or the one ``JAX_COMPILATION_CACHE_DIR`` names, and cache
    every program, however quick to compile: the window must find each
    one there.  Call before the program under test is imported, so that it
    takes the same directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_gate(chips: int) -> Dict[str, Any]:
    """The devices the cell runs on, or ``BenchError``: anything but a TPU
    backend with at least ``chips`` chips is refused, naming what it is."""
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX's default backend is "
                         f"{platform!r}; there is no CPU fallback")
    n = jax.device_count()
    if n < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds {n}")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peaks_of(kind: str, path: pathlib.Path = BENCH / "peaks.json"
             ) -> Dict[str, float]:
    table = load_json(path)["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in {path.name} "
                         f"(has {sorted(table)}); add its peaks with their "
                         f"source")
    return table[kind]


def peak_bytes_in_use(chips: int) -> Optional[int]:
    """The peak on the fullest chip the cell uses."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileClock:
    """Count JAX's backend compiles, and sum the seconds of its compile
    events, while open (over every thread)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.by_fun: collections.Counter = collections.Counter()

    def _on_event(self, event: str, duration: float, **meta) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.by_fun[meta.get("fun_name", "?")] += 1

    @property
    def compiles(self) -> int:
        return sum(self.by_fun.values())

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


# -- statistics --------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]) of all values; an item
    that failed is ``inf`` and ranks last."""
    if not values:
        return math.inf
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


# -- per-layer metric readers --------------------------------------------------

@dataclasses.dataclass
class Window:
    """What the readers under ``bench/metrics/`` read: the measured window
    and what was counted in it.

    ``stages`` holds, per stage, the telemetry histograms' differences over
    the window: ``service_count``/``service_sum`` and ``wait_count``/
    ``wait_sum``/``wait_buckets``/``wait_bounds``.  ``work`` holds the
    runner's counts from shapes (operations, bytes, rows, tokens) keyed by
    what they belong to.  ``trace`` is the reduced device trace of a
    ``--trace 1`` run, else None."""

    seconds: float
    peak: Dict[str, float]
    stages: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    work: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Any = None


def read_metric(name: str, window: Window, root: pathlib.Path = ROOT
                ) -> Optional[float]:
    """Run the reader ``bench/metrics/<name>.py``; None where it finds
    nothing to read."""
    mod = load_module(root / "bench" / "metrics" / f"{name}.py",
                      f"bench_metric_{name}")
    value = mod.read(window)
    return None if value is None else float(value)
