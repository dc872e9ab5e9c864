"""Shared fixtures of the benchmark's own tests: the repository's root and
``src`` on the path, and a CPU cell at a tiny size that leaves JAX's
configuration as it found it."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny sizes of each configuration and traffic mix, for the CPU
SMALL_CONFIG = {
    "gal-linear-m8": {"n_rows": 4096},
    "gal-linear-m8-serve": {"n_rows": 4096},
    "gal-lm-stablelm-1.6b-m2": {
        "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
        "d_ff": 256, "vocab": 1024, "seq": 64, "dtype": "float32"},
}
SMALL_TRAFFIC = {
    "fit_loop": {"holdout_rows": 512},
    "serve_open_loop": {"pool_rows": 2048,
                        "rate_per_s": 100},
    "lm_fit_loop": {},
}
_JAX_KEYS = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_default_matmul_precision")


@pytest.fixture
def small_cells(monkeypatch, tmp_path):
    """``bench.lib.harness`` with every configuration and mix cut to a CPU
    size, the cells kept for later (``bench/later/*.json``) added to the
    spec, v5e peaks for any device, no persistent compile cache, and
    outputs under ``tmp_path``; JAX's settings are put back afterwards."""
    import jax
    from bench.lib import harness
    saved = {k: getattr(jax.config, k) for k in _JAX_KEYS}
    load_config, load_traffic = harness.load_config, harness.load_traffic
    load_peaks, load_spec = harness.load_peaks, harness.load_spec

    def spec(path=harness.SPEC_PATH):
        out = load_spec(path)
        for f in sorted((harness.BENCH / "later").glob("*.json")):
            later = json.loads(f.read_text())
            for group in ("configs", "workloads", "end_to_end", "per_layer"):
                out[group] = out[group] + later[group]
        return out

    def config(spec, name, root=harness.ROOT):
        c = dict(load_config(spec, name, root))
        small = SMALL_CONFIG[name]
        c.update(small)
        c["reduced"] = sorted(set(c["reduced"]) | set(small))
        return c

    def traffic(name, bench=harness.BENCH):
        return dict(load_traffic(name, bench), **SMALL_TRAFFIC[name])

    monkeypatch.setattr(harness, "load_spec", spec)
    monkeypatch.setattr(harness, "load_config", config)
    monkeypatch.setattr(harness, "load_traffic", traffic)
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind, path=harness.PEAKS_PATH:
                        load_peaks("TPU v5 lite", path))
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield harness
    for k, v in saved.items():
        jax.config.update(k, v)
