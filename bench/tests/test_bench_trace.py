"""The reduction from the profiler's trace to busy time, idle gaps and the
breakdown: on hand-made intervals, and on a small recorded trace kept
beside this file."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_idle_by_hand():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert tr.union_length(spans) == pytest.approx(3.0)
    assert tr.idle_intervals(spans, (0.0, 5.0)) == [(2.0, 3.0), (4.0, 5.0)]


def test_busy_share_and_gap_names_by_hand():
    ops = {0: [tr.Op("fusion", "jit_run", 1.0, 2.0),
               tr.Op("fusion", "jit_run", 4.0, 5.0),
               tr.Op("all-reduce", "jit_run", 4.5, 6.0)],
           1: [tr.Op("fusion", "jit_run", 1.0, 1.5)]}
    spans = [("window", 0.0, 10.0), ("fit", 0.0, 3.0), ("fit", 3.0, 9.0),
             ("org_init", 2.5, 3.5)]
    t = tr.Trace(window=(0.0, 10.0), ops=ops, spans=spans)
    assert t.pace_device == 0
    assert t.busy(0) == pytest.approx(3.0)
    assert t.idle_share() == pytest.approx(0.7)
    assert t.busy_s == pytest.approx((3.0 + 0.5) / 2)
    assert t.module_busy() == {"jit_run": pytest.approx(3.0)}
    assert t.op_seconds(pred=lambda o: "all-reduce" in o.name) == 1.5
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(2.0)]
    # gaps 6..10, 2..4 and 0..1, each named by the shortest span open at
    # its middle (at 3.0 both fits and org_init are open)
    assert b["idle_gaps"] == [["fit", pytest.approx(4.0)],
                              ["org_init", pytest.approx(2.0)],
                              ["fit", pytest.approx(1.0)]]


def test_a_trace_recorded_on_the_cpu():
    """Host spans and the window read from a real ``.xplane.pb``; a CPU
    has no TPU device plane, so nothing is busy and no share is made up."""
    path = DATA / "cpu_tiny.xplane.pb"
    assert path.stat().st_size < 1 << 20
    t = tr.read_xplane(path)
    names = [name for name, _, _ in t.spans]
    assert names.count("fit") == 3 and names.count("submit") == 3
    assert t.window_s > 0
    assert all(t.window[0] <= a <= b <= t.window[1] + 1e-6
               for name, a, b in t.spans if name != "window")
    assert t.ops == {} and t.pace_device is None
    assert t.idle_share() is None and t.busy_s == 0.0
    assert t.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_a_trace_recorded_on_the_chip():
    """A ``.xplane.pb`` recorded on one TPU v5e: two small programs, each
    run three times under a ``fit`` and a ``submit`` span. Its operations
    carry no ``hlo_module``; each is given the program whose run it starts
    in, from the device's ``XLA Modules`` line."""
    path = DATA / "tpu_tiny.xplane.pb"
    assert path.stat().st_size < 1 << 20
    t = tr.read_xplane(path)
    names = [name for name, _, _ in t.spans]
    assert names.count("fit") == 3 and names.count("submit") == 3
    assert list(t.ops) == [0] and len(t.ops[0]) == 12
    assert t.pace_device == 0
    busy = t.busy(0)
    assert 0 < busy < t.window_s
    assert t.busy_s == pytest.approx(busy)
    assert t.idle_share() == pytest.approx(1 - busy / t.window_s)
    modules = t.module_busy()
    assert len(modules) == 2 and all(m.startswith("jit_") for m in modules)
    assert sum(modules.values()) == pytest.approx(busy)
    b = t.breakdown()
    assert sum(s for _, s in b["device_ops"]) == pytest.approx(busy)
    assert 0 < len(b["idle_gaps"]) <= 10
