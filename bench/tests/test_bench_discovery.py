"""A new configuration, traffic mix, runner and per-layer metric are found
by name from new files and new BENCHMARK.json entries alone, and a runner
refuses a configuration it has no reference for."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def test_added_files_are_found_by_name(tmp_path):
    from bench.lib import harness
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns(".cache", ".out",
                                                  "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (bench / "configs" / "gal-linear-m16.json").write_text(json.dumps(
        dict(json.loads((bench / "configs" / "gal-linear-m8.json")
                        .read_text()), name="gal-linear-m16", n_orgs=16,
             n_features=256)))
    (bench / "traffic" / "fit_loop_short.json").write_text(json.dumps(
        {"runner": "fit_loop", "holdout_rows": 128}))
    (bench / "traffic" / "idle.json").write_text(json.dumps(
        {"runner": "idle_loop", "pause_s": 0.01}))
    (bench / "runners" / "idle_loop.py").write_text(
        "class Runner:\n"
        "    def __init__(self, cfg, traffic, seed, devices):\n"
        "        self.pause = traffic['pause_s']\n")
    (bench / "metrics" / "fits_in_window.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('fits')\n")
    spec["configs"].append({"name": "gal-linear-m16", "source": "x",
                            "file": "bench/configs/gal-linear-m16.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "linear-m16-fit",
                              "config": "gal-linear-m16",
                              "traffic": "fit_loop_short", "chips": 1,
                              "why": "x"})
    spec["end_to_end"][0]["workloads"].append("linear-m16-fit")
    spec["per_layer"].append({"name": "fits_in_window", "unit": "fits",
                              "better": "higher", "source": "host_clock",
                              "layer": "whole fit",
                              "moves": "fit_rounds_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = harness.load_spec(tmp_path / "BENCHMARK.json")
    cell = harness.find_cell(spec, "linear-m16-fit")
    assert harness.load_config(spec, cell["config"], tmp_path)["n_orgs"] == 16
    traffic = harness.load_traffic(cell["traffic"], bench)
    assert harness.load_runner(traffic["runner"], bench).__name__ == "Runner"
    # a mix that brings a runner of its own
    idle = harness.load_traffic("idle", bench)
    run = harness.load_runner(idle["runner"], bench)(None, idle, 1, [])
    assert run.pause == 0.01
    layer = {m["name"] for m in harness.cell_metrics(spec, cell["name"],
                                                     "per_layer")}
    # listed by its moves alone, in every cell that reports that metric
    assert "fits_in_window" in layer
    assert harness.load_reader("fits_in_window", bench)(
        {"counters": {"fits": 3}}) == 3
    e2e = {m["name"] for m in harness.cell_metrics(spec, cell["name"],
                                                   "end_to_end")}
    assert e2e == {"fit_rounds_per_s", "setup_s"}


@pytest.mark.parametrize("key,value", [("model", "gbsvm"),
                                       ("loss", "logistic"),
                                       ("eta_method", "golden")])
def test_tabular_runner_refuses_what_it_has_no_reference_for(key, value):
    from bench.lib import harness
    spec = harness.load_spec()
    cfg = dict(harness.load_config(spec, "gal-linear-m8"), **{key: value})
    runner = harness.load_runner("fit_loop")
    with pytest.raises(ValueError, match=key):
        runner(cfg, harness.load_traffic("fit_loop"), 1, [])
