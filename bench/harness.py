"""Find a cell's files by name and run it: set-up, window, check, trace.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``  — the configuration as it is run;
* ``bench/traffic/<traffic>.json`` — the mix, read by ``bench.generator``;
* ``bench/metrics/<metric>.py``    — a reader with ``read(ctx)``, which
  returns the metric's value or ``None`` where it finds nothing to read;
* ``bench/limits/<cell>.json``     — the limit of each number compared.

So a later change adds a cell, a mix or a metric with new files and new
entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import random
import shutil
import tempfile
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_entry(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; choose from "
                   f"{[c['name'] for c in bench['workloads']]}")


def config_path(cell: dict) -> Path:
    return BENCH / "configs" / f"{cell['config']}.json"


def traffic_path(cell: dict) -> Path:
    return BENCH / "traffic" / f"{cell['traffic']}.json"


def limits_path(cell: dict) -> Path:
    return BENCH / "limits" / f"{cell['name']}.json"


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = metric_path(name)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _in_cell(metric: dict, cell: str, bench: dict) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return "workloads" not in e2e or cell in e2e["workloads"]


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _in_cell(m, cell, bench)]


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["per_layer"] if _in_cell(m, cell, bench)]


def peaks_for(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(peaks['devices'])})")
    return peaks["devices"][kind]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache``, for every program however short its compile, so that
    only a cell's first run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def chips(n: int):
    """The first ``n`` TPU devices; :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return devices[:n]


class CompileEvents:
    """Counts JAX's compile requests and persistent-cache hits and misses."""

    NAMES = {"/jax/core/compile/backend_compile_duration": "compiles",
             "/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.counts: Counter = Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, *args, **kwargs):
        if name in self.NAMES:
            self.counts[self.NAMES[name]] += 1

    def _duration(self, name, duration, *args, **kwargs):
        self._event(name)

    def snapshot(self) -> Counter:
        return Counter({k: self.counts[k] for k in self.NAMES.values()})


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: dict
    config: dict
    traffic: dict
    peaks: dict
    trace: object            # profile_reduce.Trace, or None
    counters: dict           # the program's own counters, by name
    window: dict             # what the generator measured


def log(msg: str) -> None:
    print(msg, flush=True)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return max(peaks)


def compare(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit: a number passes where it is at most
    its limit (NaN never passes)."""
    checks, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} (limits: {limits})")
        limit = limits[name]["limit"]
        passed = value <= limit and not math.isnan(value)
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict,
             limits: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: dict | None,
             driver_kw: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict, with the
    numbers compared under ``checks``, its last key."""
    import jax

    from bench import generator, profile_reduce

    events = CompileEvents()
    driver = generator.driver_for(config, traffic, devices,
                                  **(driver_kw or {}))
    driver.prepare(seed)
    log(f"[setup] {cell['name']} seed={seed} {driver.describe()}")
    before = events.snapshot()
    log(f"[cache] dir={CACHE_DIR} set-up: {dict(before)}")
    rng = random.Random(seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win = driver.window(seconds, rng)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = events.snapshot() - before
    setup_s = win["start"] - t_start
    log(f"[cache] in window: compiles={in_window['compiles']} "
        f"hits={in_window['cache_hits']} misses={in_window['cache_misses']}")
    log(f"[window] {json.dumps(win['info'])}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    counters = driver.counters()
    driver.release()
    numbers = driver.check()
    ok, checks = compare(numbers, limits)
    metrics: dict = {}
    breakdown = None
    if trace:
        tr = profile_reduce.load(profile_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        ctx = Context(cell, config, traffic, peaks or {}, tr, counters, win)
        for m in per_layer_for(bench, cell["name"]):
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        log(f"[trace] devices={sorted(tr.devices)} window_s={tr.window_s} "
            f"busy_s={device['busy_s']} "
            f"kernel_launches={len(tr.kernel_ops(sorted(tr.devices)[0]))}")
    else:
        values = {"setup_s": setup_s, **{k: v for k, v in win.items()
                                         if k not in ("info", "start")}}
        for m in end_to_end_for(bench, cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": ok, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
