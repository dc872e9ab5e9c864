"""BENCHMARK.json keeps to its rules, and everything it names is there."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n\r]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRIC_KEYS = {"name", "unit", "better", "source", "bound", "workloads",
               "layer", "moves"}


# entries kept for later (bench/later/*.json) keep the same rules, so that
# they can move back into BENCHMARK.json as they are
LATER = [json.loads(f.read_text())
         for f in sorted((ROOT / "bench" / "later").glob("*.json"))]


def all_names():
    for spec in [SPEC] + LATER:
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for entry in spec[group]:
                yield entry["name"]
        for cell in spec["workloads"]:
            yield cell["config"]
            yield cell["traffic"]
        for cfg in spec["configs"]:
            yield from cfg["reduced"]


def test_top_level_keys_and_size():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in SPEC["paths"])
    assert len(SPEC["command"]) <= 32
    assert all(TEXT.match(w) for w in SPEC["command"])


@pytest.mark.parametrize("name", sorted(set(all_names())))
def test_name_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("group,keys,optional", [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
])
def test_entries_have_exactly_their_keys(group, keys, optional):
    for spec in [SPEC] + LATER:
        for entry in spec[group]:
            assert keys <= set(entry) <= keys | optional, (group, entry)
            if "why" in entry:
                assert TEXT.match(entry["why"]), entry


def test_units_better_sources_and_unique_names():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert set(m) <= METRIC_KEYS, m
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace"), m
        assert 0.01 <= m["bound"] <= 0.25, m
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), m
        assert TEXT.match(m["layer"]), m
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_every_named_file_exists():
    for cfg in SPEC["configs"]:
        path = ROOT / cfg["file"]
        assert path.is_file() and cfg["file"].startswith(
            tuple(p + "/" for p in SPEC["paths"])), cfg
        assert TEXT.match(cfg["source"]), cfg
    for cell in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json"
                ).is_file(), cell
        assert cell["chips"] in (1, 4) and TEXT.match(cell["why"]), cell
    for m in SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    from bench.lib import harness
    cells = {c["name"] for c in SPEC["workloads"]}
    for cell in cells:
        e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert harness.cell_metrics(SPEC, cell, "per_layer"), cell
    for m in SPEC["per_layer"]:
        for cell in m.get("workloads", ()):
            assert cell in cells
            e2e = {e["name"] for e in harness.cell_metrics(SPEC, cell,
                                                           "end_to_end")}
            assert m["moves"] in e2e, (m["name"], cell)
    used = {c["config"] for c in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_config_files_state_their_reductions():
    for entry in SPEC["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["reduced"] == entry["reduced"], entry["name"]
        assert set(cfg["reduced"]) <= set(cfg), entry["name"]
        assert cfg["matmul_precision"] in ("default", "high", "highest")
        assert cfg["limits"], entry["name"]


def test_peaks_are_keyed_by_device_kind():
    from bench.lib import harness
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
