"""Shared pieces of the benchmark: registry lookups by name, seeds, data
made on the device, device facts, compile accounting and host spans.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell, the cell names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<traffic>.json``), the mix names its kind
(``kinds/<kind>.py``) and every metric is a reader ``metrics/<name>.py``.
Adding a cell, a configuration, a mix or a metric adds files and entries;
it edits none.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in spec['workloads']]}")


def config_file(spec: dict, config: str, root: str = ROOT) -> str:
    for c in spec["configs"]:
        if c["name"] == config:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration {config!r} in BENCHMARK.json")


def load_config(spec: dict, config: str, root: str = ROOT) -> dict:
    return load_json(config_file(spec, config, root))


def load_traffic(name: str, bench_dir: str = BENCH) -> dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_module(path: str, name: str):
    """Import a file by path (metric and kind files may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str, bench_dir: str = BENCH):
    return load_module(os.path.join(bench_dir, "kinds", f"{kind}.py"),
                       f"bench_kind_{kind}")


def load_metric(name: str, bench_dir: str = BENCH):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    return load_module(path, "bench_metric_" + name.replace(".", "_"))


def cell_metrics(spec: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those listing it under ``workloads``, or listing none."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(metrics: list[dict], record: dict,
                 bench_dir: str = BENCH, *, rehearse: bool = False) -> dict:
    """Run each metric's reader on the run record; a reader that finds
    nothing returns None and the metric is left out.  A rehearsal on the
    CPU has no peaks to read: such a metric is left out there too."""
    out = {}
    for m in metrics:
        try:
            value = load_metric(m["name"], bench_dir).read(record)
        except KeyError:
            if not rehearse:
                raise
            continue
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def overrides(block: dict, rehearse: bool) -> dict:
    """A config or traffic block with its ``rehearse`` sizes applied when
    rehearsing on the CPU at a tiny size."""
    out = {k: v for k, v in block.items() if k != "rehearse"}
    if rehearse:
        out.update(block.get("rehearse", {}))
    return out


def program_seed(seed: int) -> int:
    """The driver's seeds exceed 32 signed bits; the program's PRNG keys
    take a 31-bit seed.  Deterministic in ``seed``."""
    return int(seed) % 2147483647


# --------------------------------------------------------------------------
# device facts
# --------------------------------------------------------------------------

def require_devices(n: int) -> None:
    """Exit without a result unless JAX sees ``n`` accelerator chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices; "
                         "this benchmark measures the chip only")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devs)}")


def device_info(n_used: int) -> dict:
    import jax
    devs = jax.devices()[:n_used]
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — backends without memory stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts the programs JAX compiled or fetched from its persistent cache
    and sums their seconds, and counts the cache's misses (``jax.monitoring``
    listeners), so set-up can report compile time and the window can show
    that nothing compiled inside it."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.count = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> str:
        return (f"compiling or loading {self.count} programs took "
                f"{self.secs:.3f} s, {self.misses} of them not in the "
                "persistent cache")


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def now() -> float:
    return time.perf_counter()


def latencies(record):
    """Request latencies of a serve window (s).  A failed or refused request
    counts as missing every limit: it reads as the whole window plus the
    minute the collector waits for it."""
    import numpy as np
    lat = np.asarray(record["latency_s"], np.float64)
    return np.where(np.isfinite(lat), lat, record["window_s"] + 60.0)
