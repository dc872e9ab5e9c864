"""What the program names in a traced window (``repro.utils.tracing``),
beside the reduction of ``bench/lib/trace.py``.

``trace.py`` gives the window, the device that sets the pace and its
operations; it keeps only the benchmark's own host spans and no scope.
This module adds, from the same ``.xplane.pb`` (the one ``trace.Tracer``
wrote under ``harness.OUT_DIR / "trace-<cell>"``):

* each operation's round phase: the ``gal.<phase>`` segment of its
  name-scope path, the ``tf_op`` stat of its event metadata
  (``bench/lib/xspace.py``), found by its program's id and its name;
* the program's host spans ``gal.*`` (``gal.fit``, ``gal.launch`` ...) with
  their arguments: every span of one fit carries that fit's number
  (``fit``), and a fit's top-level span the number of rounds it ran and of
  round programs it built (``rounds``, ``round_traces``).

A phase's busy time is the union of the pace device's operations in that
phase, so an operation nested in another of the same phase counts once.
Loop control between a loop's body operations carries no scope and is not
counted. Every reading is None where the program left no scope or span.

    python3 -m bench.lib.program_trace <file.xplane.pb> ...

run from the repository's root, prints the phase split, the program's
counts, the host gap split by the fit's spans and the longest idle gaps
of each recorded window.
"""
from __future__ import annotations

import functools
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace as trace_lib
from bench.lib import xspace

PROGRAM = "gal."
PHASES = ("residual", "broadcast", "local_fit", "weight_fit", "combine",
          "eta")
TOP_SPANS = ("gal.fit", "gal.fit_lm")
PHASE_SCOPE = re.compile(r"(?:^|/)gal\.(\w+)(?=/|:|$)")
# a program run's name ends in its program id:
# "jit_gal_rounds(11877974641271396384)"
PROGRAM_ID = re.compile(r"\((\d+)\)$")

Interval = Tuple[float, float]


@functools.lru_cache(maxsize=4096)
def phase_of(scope: str) -> Optional[str]:
    """The round phase ``<phase>`` of the program's scope ``gal.<phase>``
    that a name-scope path runs through, or None."""
    m = PHASE_SCOPE.search(scope)
    return m.group(1) if m else None


@dataclass
class Span:
    name: str
    start: float            # seconds, on the trace's clock
    end: float
    args: Dict[str, int] = field(default_factory=dict)


@dataclass
class ProgramTrace:
    window: Interval
    device: Optional[int]   # the device that sets the pace, if any ran
    # its operations: (start, end, round phase or None)
    ops: List[Tuple[float, float, Optional[str]]]
    scoped: bool            # whether any operation carries a program scope
    spans: List[Span]       # the program's host spans
    bench_spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def _clip(self, a: float, b: float) -> Optional[Interval]:
        lo, hi = self.window
        return (max(a, lo), min(b, hi)) if b > lo and a < hi else None

    def phase_busy(self, phase: str) -> Optional[float]:
        """Busy seconds of the pace device in one round phase; None where
        no operation carries a program scope."""
        if not self.scoped:
            return None
        return trace_lib.union_length([
            c for a, b, p in self.ops if p == phase
            for c in [self._clip(a, b)] if c])

    def phase_split(self) -> Dict[str, float]:
        """Busy seconds of each phase, and of all six together."""
        if not self.scoped:
            return {}
        out = {p: self.phase_busy(p) for p in PHASES}
        out["all"] = trace_lib.union_length([
            c for a, b, p in self.ops if p in PHASES
            for c in [self._clip(a, b)] if c])
        return out

    def fits(self) -> List[Span]:
        """The top-level span of each fit that ran in the window."""
        return [s for s in self.spans if s.name in TOP_SPANS
                and self._clip(s.start, s.end)]

    def count(self, name: str) -> Optional[int]:
        """What the window's fits recorded of one of the program's
        counters (``rounds``, ``round_traces``)."""
        fits = self.fits()
        if not fits or any(name not in s.args for s in fits):
            return None
        return sum(s.args[name] for s in fits)

    def host_gap_s(self) -> Optional[float]:
        """Idle seconds of the pace device inside each fit's top-level
        span, per fit."""
        fits = [self._clip(s.start, s.end) for s in self.fits()]
        if self.device is None or not fits:
            return None
        idle = 0.0
        for a, b in fits:
            idle += (b - a) - trace_lib.union_length(
                [(max(s, a), min(e, b)) for s, e, _ in self.ops
                 if e > a and s < b])
        return idle / len(fits)

    def host_gap_split(self) -> Dict[str, float]:
        """``host_gap_s`` split by what the host was doing: each idle
        stretch inside a fit's top-level span goes to the innermost of
        that fit's spans (those carrying its ``fit`` number) open there,
        else to the top-level span itself. Seconds per fit, by span
        name."""
        fits = self.fits()
        if self.device is None or not fits:
            return {}
        out: Dict[str, float] = {}
        for top in fits:
            a, b = self._clip(top.start, top.end)
            own = [s for s in self.spans if s is not top
                   and s.args.get("fit") == top.args.get("fit")]
            busy = [(max(s, a), min(e, b)) for s, e, _ in self.ops
                    if e > a and s < b]
            for lo, hi in trace_lib.idle_intervals(busy, (a, b)):
                cuts = sorted({lo, hi} | {t for s in own
                                          for t in (s.start, s.end)
                                          if lo < t < hi})
                for x, y in zip(cuts, cuts[1:]):
                    mid = 0.5 * (x + y)
                    open_ = [s for s in own if s.start <= mid <= s.end]
                    name = (min(open_, key=lambda s: s.end - s.start).name
                            if open_ else top.name)
                    out[name] = out.get(name, 0.0) + (y - x)
        return {k: v / len(fits) for k, v in out.items()}

    def idle_gaps(self, top: int = 10) -> List[list]:
        """The pace device's longest idle gaps in the window, each named
        by the innermost span, the program's or the benchmark's, open at
        its middle."""
        spans = ([(s.name, s.start, s.end) for s in self.spans]
                 + [s for s in self.bench_spans if s[0] != "window"])
        gaps = []
        for a, b in trace_lib.idle_intervals(
                [c for s, e, _ in self.ops for c in [self._clip(s, e)] if c],
                self.window):
            t = 0.5 * (a + b)
            open_ = [s for s in spans if s[1] <= t <= s[2]]
            name = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                    else "window")
            gaps.append([name, b - a])
        return sorted(gaps, key=lambda g: -g[1])[:top]


def _scope(scopes, module: str, name: str) -> str:
    m = PROGRAM_ID.search(module)
    pid = int(m.group(1)) if m else None
    found = scopes.get((pid, name))
    if found is None and pid is not None:
        found = scopes.get((None, name))
    return found or ""


def read(path: Path, trace: trace_lib.Trace) -> ProgramTrace:
    """The program's scopes and spans in ``path``, over ``trace``, the
    reduction ``trace.read_xplane`` made of the same file."""
    from jax.profiler import ProfileData
    dev = trace.pace_device
    ops: List[Tuple[float, float, Optional[str]]] = []
    scoped = False
    if dev is not None:
        scopes = xspace.op_scopes(path, trace_lib.DEVICE_PLANE).get(
            f"/device:TPU:{dev}", {})
        for o in trace.ops[dev]:
            scope = _scope(scopes, o.module, o.name)
            scoped = scoped or PROGRAM in scope
            ops.append((o.start, o.end, phase_of(scope)))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith(PROGRAM):
                    start = e.start_ns * 1e-9
                    spans.append(Span(name, start,
                                      start + e.duration_ns * 1e-9,
                                      {k: v for k, v in e.stats
                                       if isinstance(v, int)}))
    return ProgramTrace(trace.window, dev, ops, scoped, spans, trace.spans)


_loaded: Dict[Tuple[str, float], ProgramTrace] = {}


def load(ctx) -> Optional[ProgramTrace]:
    """The program's scopes and spans of the traced window a per-layer
    reader is given; None where the run was not traced."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    from bench.lib import harness
    paths = sorted((harness.OUT_DIR / f"trace-{ctx['cell']['name']}"
                    ).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not paths:
        return None
    key = (str(paths[-1]), paths[-1].stat().st_mtime)
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = read(paths[-1], trace)
    return _loaded[key]


def phase_ms_per_round(ctx, phase: str) -> Optional[float]:
    """Busy milliseconds of one round phase per round the program ran in
    the window."""
    prog = load(ctx)
    seconds = None if prog is None else prog.phase_busy(phase)
    rounds = None if prog is None else prog.count("rounds")
    if seconds is None or not rounds:
        return None
    return 1e3 * seconds / rounds


def summary(path: Path, top: int = 10) -> Dict[str, object]:
    trace = trace_lib.read_xplane(path)
    prog = read(path, trace)
    busy = trace.module_busy()
    return {"window_s": trace.window_s, "fits": len(prog.fits()),
            "rounds": prog.count("rounds"),
            "round_traces": prog.count("round_traces"),
            "phase_s": prog.phase_split(),
            "round_program_s": max(busy.values()) if busy else None,
            "host_gap_s": prog.host_gap_s(),
            "host_gap_split_s": prog.host_gap_split(),
            "idle_gaps": prog.idle_gaps(top)}


def main(argv: Sequence[str]) -> int:
    for p in argv:
        print(json.dumps(summary(Path(p))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
