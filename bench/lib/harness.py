"""The benchmark's spine: what every cell shares.

``BENCHMARK.json`` names the cells. A cell names a configuration, found
as ``bench/configs/<config>.json``, and a traffic mix, found as
``bench/traffic/<traffic>.json``, a data file of parameters whose
``runner`` key names the runner that drives the window with them,
``bench/runners/<runner>.py`` (its class ``Runner``). Each per-layer
metric is a reader of its own, ``bench/metrics/<metric>.py``, with a
``read(ctx)`` that returns a number or None. Adding a cell, a
configuration, a mix, a runner or a metric is adding files and entries; no
file here changes.

A runner is built from (configuration, traffic, seed, devices) and runs in
four steps that ``bench/run.py`` calls in order:

  setup()         make the inputs from the seed and warm every shape
  window(seconds) the measured work; returns the end-to-end readings
  release()       keep what the check needs, free the program's state
  check()         compare with the plain reference: [{name, value, limit}]

Its ``counters`` hold what the per-layer readers take from the program,
and its ``control()`` gives the readings of the control (``bench/control.py``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC_PATH = ROOT / "BENCHMARK.json"
PEAKS_PATH = BENCH / "peaks.json"
# fixed paths inside the checkout: the compile cache's directory is part of
# its key, so a directory that moved would never hit
CACHE_DIR = BENCH / ".cache" / "jax"
OUT_DIR = BENCH / ".out"


def prepare_env() -> None:
    """Before JAX loads: the TPU runtime's own logs stay off, so that a run
    writes nothing to a fixed path outside its checkout."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's own record."""
    try:
        import psutil
        return time.time() - psutil.Process().create_time()
    except ImportError:                      # pragma: no cover
        return time.process_time()


def load_spec(path: Path = SPEC_PATH) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                     f"({[c['name'] for c in spec['workloads']]})")


def config_entry(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for entry in spec["configs"]:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"bench: no configuration {name!r} in BENCHMARK.json")


def load_config(spec: Dict[str, Any], name: str,
                root: Path = ROOT) -> Dict[str, Any]:
    with open(root / config_entry(spec, name)["file"]) as f:
        return json.load(f)


def load_traffic(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    with open(bench / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _load_module(path: Path):
    """A module loaded by its path, since a file's name may hold dots."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _load_module(bench / "metrics" / f"{metric}.py").read


def load_runner(name: str, bench: Path = BENCH):
    """The ``Runner`` class of ``bench/runners/<name>.py``."""
    return _load_module(bench / "runners" / f"{name}.py").Runner


def cell_metrics(spec: Dict[str, Any], cell: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") this cell
    reports: those that list it, and those without a ``workloads`` key
    that move an end-to-end metric the cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load_peaks(kind: str, path: Path = PEAKS_PATH) -> Dict[str, Any]:
    """The published peaks of one chip of ``kind`` (``device_kind``). An
    unknown kind is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in {path.name} "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


def check_devices(chips: int) -> Optional[str]:
    """None when JAX sees at least ``chips`` TPU chips, else the reason."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU, JAX found {devs[0].platform!r}"
    if len(devs) < chips:
        return f"needs {chips} TPU chip(s), JAX found {len(devs)}"
    return None


class OpenCell(NamedTuple):
    spec: Dict[str, Any]
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    runner: Any
    devices: List[Any]


def open_cell(workload: str, require_tpu: bool = True) -> OpenCell:
    """What every process that drives a cell does before its runner: find
    the cell, its configuration, its mix and the mix's runner by name,
    refuse a machine without the chips the cell asks for, and set JAX up
    (compile cache, matmul precision). Exits non-zero on a refusal."""
    spec = load_spec()
    cell = find_cell(spec, workload)
    if require_tpu:
        why = check_devices(cell["chips"])
        if why is not None:
            raise SystemExit(f"bench: {why}")
    cfg = load_config(spec, cell["config"])
    traffic = load_traffic(cell["traffic"])
    cache = configure_jax(cfg["matmul_precision"])
    import jax
    devices = jax.devices()[:cell["chips"]]
    log(f"{cell['name']}: {devices[0].device_kind} x{len(devices)}, "
        f"compile cache {cache}")
    return OpenCell(spec, cell, cfg, traffic,
                    load_runner(traffic["runner"]), devices)


def configure_jax(precision: str) -> str:
    """Compile cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached whatever its
    compile time, and the matmul precision the configuration states."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision", precision)
    return cache


class CompileEvents:
    """Counts and host seconds of JAX's tracing, lowering and backend
    compile (or persistent-cache load), from its monitoring events."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }
    COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax
        self.totals = {v: 0.0 for v in self.DURATIONS.values()}
        self.totals.update({v: 0 for v in self.COUNTS.values()})
        self.totals["backend_compiles"] = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        key = self.DURATIONS.get(event)
        if key is not None:
            self.totals[key] += duration
            if key == "compile_s":
                self.totals["backend_compiles"] += 1

    def _event(self, event, **_):
        key = self.COUNTS.get(event)
        if key is not None:
            self.totals[key] += 1

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)

    def since(self, mark: Dict[str, float]) -> Dict[str, float]:
        return {k: self.totals[k] - mark[k] for k in self.totals}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def device_info(devices, peak: Optional[int]) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def compare(readings: Dict[str, float], limits: Dict[str, float]
            ) -> List[dict]:
    """The readings that have a limit are compared; the rest are logged."""
    for k in sorted(set(readings) - set(limits)):
        log(f"reading {k}: {readings[k]!r} (not compared)")
    return [{"name": k, "value": readings[k], "limit": limits[k]}
            for k in limits]


def checks_ok(checks: List[dict]) -> bool:
    """Each check is ``{"name", "value", "limit"}``; it holds when its
    value is a finite number at or below its limit."""
    import math
    return all(c["value"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks)


def emit(result: Dict[str, Any], checks: List[dict]) -> None:
    """The checks as the last lines on standard error, and the result as
    the last line of standard output, with the checks as its last key."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def timed_loop(seconds: float, unit: Callable[[], Any]) -> Dict[str, float]:
    """Run ``unit`` back to back until ``seconds`` have passed; the window
    ends when the last unit ends, so every unit counts whole."""
    t0 = time.perf_counter()
    n = 0
    while True:
        unit()
        n += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return {"units": n, "elapsed_s": elapsed}


def fit_loop(run, seconds: float, fit) -> Dict[str, Any]:
    """Whole fits back to back for ``seconds``; the last fit's outputs go
    before the next starts, as a user's next collaboration would not keep
    them. Counts fits, rounds and seconds into ``run.counters``."""
    def unit():
        run.result = None
        run.result = fit()
        if run.result.engine != run.cfg["engine"]:
            raise RuntimeError(f"the fit ran on engine {run.result.engine!r}")
    loop = timed_loop(seconds, unit)
    run.counters.update(fits=loop["units"],
                        rounds=loop["units"] * run.cfg["rounds"],
                        elapsed_s=loop["elapsed_s"])
    return run.counters
