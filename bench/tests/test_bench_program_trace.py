"""What the program names in a traced window (``bench/lib/program_trace.py``
and ``bench/lib/xspace.py``): round phases from the device operations'
name-scope paths, the program's host spans and the counts its fits record
in them. On hand-made windows, on the two traces recorded before the
program had spans or scopes, and on a scoped two-round fit recorded on the
chip."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from bench.lib import harness, xspace
from bench.lib import program_trace as pt
from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"
PHASE_READERS = [f"{p}_device_ms.{c}" for p in ("weight_fit", "eta",
                                                "local_fit")
                 for c in ("fit", "lm")]
NEW_READERS = PHASE_READERS + [f"{m}.{c}" for m in ("host_gap_ms",
                                                    "round_traces_per_fit")
                               for c in ("fit", "lm")]


def test_metadata_reader_on_the_chip_trace():
    """The ``tf_op`` stat of the device plane's event metadata, read by the
    wire-format reader and joined to each operation by program and name."""
    path = DATA / "tpu_tiny.xplane.pb"
    scopes = xspace.op_scopes(path, tr.DEVICE_PLANE)["/device:TPU:0"]
    fusion = {s for (_, name), s in scopes.items()
              if name.startswith("%fusion = ")}
    assert fusion == {"jit(<lambda>)/dot_general:"}
    t = tr.read_xplane(path)
    by_name = {o.name.split(" ")[0]: pt._scope(scopes, o.module, o.name)
               for o in t.ops[0]}
    assert by_name["%fusion"] == "jit(<lambda>)/dot_general:"
    assert by_name["%add_reduce_fusion"] == "jit(<lambda>)/reduce_sum:"
    assert by_name["%copy-start"] == ""


@pytest.mark.parametrize("scope,phase", [
    ("jit(run)/while/body/closed_call/gal.eta/while/body/add:", "eta"),
    ("jit(run)/while/body/gal.eta:", "eta"),
    ("gal.weight_fit/transpose(jvp())/mul:", "weight_fit"),
    ("jit(run)/while/body/gal.eta_x/add:", "eta_x"),
    ("jit(run)/while/body/xgal.eta/add:", None),
    ("jit(<lambda>)/dot_general:", None),
    ("", None),
])
def test_phase_of_a_scope_path(scope, phase):
    assert pt.phase_of(scope) == phase


def _window():
    """Two fits by hand on one device: the weight fit's loop body with an
    operation nested in another of the same phase, a line search, a copy
    with no phase; the benchmark's spans and the program's, whose
    top-level spans record 10 rounds and 1 round-program build each."""
    ops = [(1.0, 2.0, "weight_fit"), (1.2, 1.5, "weight_fit"),
           (1.6, 1.9, "weight_fit"), (2.2, 2.6, "eta"), (5.0, 5.5, None),
           (5.5, 6.0, "weight_fit")]
    counts = {"fit": 1, "rounds": 10, "round_traces": 1}
    spans = [pt.Span("gal.fit", 0.1, 3.9, counts),
             pt.Span("gal.launch", 0.2, 0.9, {"fit": 1}),
             pt.Span("gal.sync", 1.0, 3.5, {"fit": 1}),
             pt.Span("gal.fit", 4.1, 8.9, dict(counts, fit=2)),
             pt.Span("gal.launch", 4.2, 4.8, {"fit": 2}),
             pt.Span("gal.finalize", 6.1, 8.8, {"fit": 2})]
    bench = [("window", 0.0, 10.0), ("fit", 0.0, 4.0), ("fit", 4.0, 9.0)]
    return pt.ProgramTrace((0.0, 10.0), 0, ops, True, spans, bench)


def test_phase_busy_counts_nested_operations_once():
    prog = _window()
    assert prog.phase_busy("weight_fit") == pytest.approx(1.0 + 0.5)
    assert prog.phase_busy("eta") == pytest.approx(0.4)
    assert prog.phase_busy("residual") == 0.0
    assert prog.phase_split()["all"] == pytest.approx(1.9)
    assert prog.count("rounds") == 20 and prog.count("round_traces") == 2
    assert prog.count("fit_count_never_recorded") is None


def test_host_gap_and_idle_gap_names_by_hand():
    """Fit 1 spans 0.1..3.9 with the device busy 1.0..2.0 and 2.2..2.6
    (2.4 s idle); fit 2 spans 4.1..8.9 with it busy 5.0..6.0 (3.8 s idle).
    Each idle gap takes the name of the innermost span open at its
    middle."""
    prog = _window()
    assert [s.start for s in prog.fits()] == [0.1, 4.1]
    assert prog.host_gap_s() == pytest.approx((2.4 + 3.8) / 2)
    assert prog.idle_gaps() == [["gal.finalize", pytest.approx(4.0)],
                                ["gal.fit", pytest.approx(2.4)],
                                ["gal.launch", pytest.approx(1.0)],
                                ["gal.sync", pytest.approx(0.2)]]


def test_host_gap_split_by_each_fits_spans_by_hand():
    """Fit 1's idle 2.4 s: 0.7 under its gal.launch (0.2..0.9), 1.1 under
    its gal.sync (2.0..2.2, 2.6..3.5), 0.6 under no span of its own; fit
    2's 3.8 s: 0.6 under gal.launch, 2.7 under gal.finalize, 0.5 under
    none. Per fit."""
    prog = _window()
    split = prog.host_gap_split()
    assert split == {"gal.fit": pytest.approx(0.55),
                     "gal.launch": pytest.approx(0.65),
                     "gal.sync": pytest.approx(0.55),
                     "gal.finalize": pytest.approx(1.35)}
    assert sum(split.values()) == pytest.approx(prog.host_gap_s())
    # a span is its fit's by number, not by time: fit 2's spans
    # renumbered to another fit leave its idle time to gal.fit
    for s in prog.spans[4:]:
        s.args["fit"] = 3
    assert prog.host_gap_split()["gal.fit"] == pytest.approx(
        (0.6 + 3.8) / 2)


def test_readers_on_a_window_by_hand(monkeypatch):
    prog = _window()
    monkeypatch.setattr(pt, "load", lambda ctx: prog)
    read = harness.load_reader
    assert read("weight_fit_device_ms.fit")({}) == pytest.approx(75.0)
    assert read("eta_device_ms.lm")({}) == pytest.approx(20.0)
    assert read("local_fit_device_ms.fit")({}) == 0.0
    assert read("host_gap_ms.lm")({}) == pytest.approx(3100.0)
    assert read("round_traces_per_fit.fit")({}) == 1.0
    unscoped = pt.ProgramTrace(prog.window, 0, prog.ops, False, prog.spans)
    monkeypatch.setattr(pt, "load", lambda ctx: unscoped)
    assert read("weight_fit_device_ms.fit")({}) is None
    assert read("round_traces_per_fit.lm")({}) == 1.0


def _ctx(monkeypatch, tmp_path, data):
    """What ``bench/run.py`` hands a reader after a traced window: the
    reduction of the trace the Tracer left under the output directory."""
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    target = tmp_path / "trace-linear-m8-fit" / "run" / f"{data}.xplane.pb"
    target.parent.mkdir(parents=True)
    shutil.copy(DATA / f"{data}.xplane.pb", target)
    return {"cell": {"name": "linear-m8-fit"},
            "trace": tr.read_xplane(target)}


@pytest.mark.parametrize("reader", NEW_READERS)
@pytest.mark.parametrize("data", ["cpu_tiny", "tpu_tiny"])
def test_new_readers_read_nothing_without_the_programs_names(
        monkeypatch, tmp_path, reader, data):
    """What a program without spans or scopes leaves, as the parent commit
    does: nothing to read, and no error."""
    ctx = _ctx(monkeypatch, tmp_path, data)
    assert harness.load_reader(reader)(ctx) is None
    assert harness.load_reader(reader)({"trace": None}) is None


def _merged(intervals):
    """Total length of a set of intervals, merged by hand."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_a_scoped_fit_recorded_on_the_chip(monkeypatch, tmp_path):
    """``scoped_fit.xplane.pb`` (``data/record_scoped_fit.py``, one TPU
    v5e): one two-round ``gal.fit`` of four ridge organizations, whose
    span records 2 rounds and 1 round-program build."""
    assert (DATA / "scoped_fit.xplane.pb").stat().st_size < 1 << 20
    ctx = _ctx(monkeypatch, tmp_path, "scoped_fit")
    t = ctx["trace"]
    prog = pt.load(ctx)
    fit, = prog.fits()
    assert (fit.name, fit.args["rounds"], fit.args["round_traces"]) == (
        "gal.fit", 2, 1)
    # each phase by hand: the merged intervals of the pace device's
    # operations whose name-scope path runs through gal.<phase>, per round
    scopes = xspace.op_scopes(DATA / "scoped_fit.xplane.pb",
                              tr.DEVICE_PLANE)["/device:TPU:0"]
    for phase, ms in (("weight_fit", 0.3621555), ("eta", 0.0760475),
                      ("local_fit", 0.0153555)):
        hand = _merged((o.start, o.end) for o in t.ops[0]
                       if f"/gal.{phase}/" in pt._scope(scopes, o.module,
                                                        o.name)) * 1e3 / 2
        assert hand == pytest.approx(ms, rel=1e-5)
        for cell in ("fit", "lm"):
            got = harness.load_reader(f"{phase}_device_ms.{cell}")(ctx)
            assert got == pytest.approx(hand, rel=1e-9)
    split = prog.phase_split()
    assert split["broadcast"] == 0.0          # no privacy, no wire cast
    assert split["all"] <= max(t.module_busy().values())
    # the fit's span less the device's busy time inside it: 576.13 ms
    hand = (fit.end - fit.start) - _merged(
        (max(o.start, fit.start), min(o.end, fit.end)) for o in t.ops[0]
        if o.end > fit.start and o.start < fit.end)
    assert hand * 1e3 == pytest.approx(576.13325, rel=1e-6)
    for cell in ("fit", "lm"):
        assert harness.load_reader(f"host_gap_ms.{cell}")(ctx) == \
            pytest.approx(hand * 1e3, rel=1e-9)
        assert harness.load_reader(f"round_traces_per_fit.{cell}")(ctx) \
            == 1.0
    assert prog.idle_gaps()[0][0] == "gal.launch"
    split = prog.host_gap_split()
    assert sum(split.values()) == pytest.approx(hand, rel=1e-9)
    assert max(split, key=split.get) == "gal.launch"
    # the two clocks agree: the round program's run starts inside the
    # fit's gal.launch span, which enqueued it, and ends inside gal.sync
    spans = {s.name: (s.start, s.end) for s in prog.spans}
    run_ops = [o for o in t.ops[0] if o.module.startswith("jit_gal_rounds(")]
    start, end = min(o.start for o in run_ops), max(o.end for o in run_ops)
    launch, sync = spans["gal.launch"], spans["gal.sync"]
    assert launch[0] < start < launch[1] and sync[0] < end < sync[1]


def test_summary_of_a_recorded_window(capsys):
    assert pt.main([str(DATA / "scoped_fit.xplane.pb")]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["fits"] == 1 and out["rounds"] == 2
    assert out["phase_s"]["all"] <= out["round_program_s"]


def test_a_traced_run_on_the_cpu(small_cells, capsys):
    """A whole traced run of the linear cell at its CPU size: the fits'
    spans give the round-program builds per fit; the CPU has no device
    plane, so no device reading is made up."""
    import bench.run as bench_run
    args = bench_run.parse(["--workload", "linear-m8-fit", "--seed",
                            "3000000001", "--seconds", "0.5", "--trace",
                            "1"])
    assert bench_run.run_cell(args, require_tpu=False) == 0
    metrics = json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "metrics"]
    assert metrics["round_traces_per_fit.fit"]["value"] == 1.0
    assert not set(metrics) & {m for m in NEW_READERS
                               if not m.startswith("round_traces")}
