"""From the profiler's trace to the numbers the per-layer readers use.

The window runs under ``jax.profiler``; its ``.xplane.pb`` is read back with
``jax.profiler.ProfileData`` (nothing but JAX). Device planes are the
``/device:TPU:<n>`` planes; their ``XLA Ops`` line holds one event per
operation that ran, and their ``XLA Modules`` line one event per program
run, which names the program of each operation that starts inside it (or
the operation's own ``hlo_module``, where it has one). The host plane holds the
benchmark's own ``TraceAnnotation`` spans (``window``, ``fit``, ``submit``
...), on the same clock, so an idle gap on a device can be named by what
the host was doing in it.

Busy time is the union of a device's operation intervals inside the
window. The device that sets the pace is the one with the most busy time.
"""
from __future__ import annotations

import bisect
import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the spans the benchmark's own files put around calls into each layer
HOST_SPANS = ("window", "fit", "org_init", "submit", "result", "warmup",
              "make_data")

Interval = Tuple[float, float]


@dataclass
class Op:
    name: str
    module: str
    start: float            # seconds, on the trace's clock
    end: float


@dataclass
class Trace:
    window: Interval
    ops: Dict[int, List[Op]]                 # device id -> its operations
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, ops: Iterable[Op]) -> List[Interval]:
        lo, hi = self.window
        return [(max(o.start, lo), min(o.end, hi)) for o in ops
                if o.end > lo and o.start < hi]

    def busy(self, device: int, pred=None) -> float:
        ops = self.ops.get(device, [])
        if pred is not None:
            ops = [o for o in ops if pred(o)]
        return union_length(self._clip(ops))

    @property
    def pace_device(self) -> Optional[int]:
        if not self.ops:
            return None
        return max(self.ops, key=self.busy)

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran operations."""
        if not self.ops:
            return 0.0
        return sum(self.busy(d) for d in self.ops) / len(self.ops)

    def idle_share(self) -> Optional[float]:
        dev = self.pace_device
        if dev is None or self.window_s <= 0:
            return None
        return 1.0 - self.busy(dev) / self.window_s

    def module_busy(self) -> Dict[str, float]:
        """Busy seconds of each program on the pace device."""
        dev = self.pace_device
        if dev is None:
            return {}
        names = {o.module for o in self.ops[dev]}
        return {m: self.busy(dev, lambda o, m=m: o.module == m)
                for m in names}

    def op_seconds(self, device: Optional[int] = None, pred=None) -> float:
        """Summed durations (not the union) of matching operations."""
        dev = self.pace_device if device is None else device
        if dev is None:
            return 0.0
        return sum(b - a for a, b in self._clip(
            o for o in self.ops.get(dev, []) if pred is None or pred(o)))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time on the pace device,
        and its longest idle gaps, each named by the innermost host span
        open at the gap's middle."""
        dev = self.pace_device
        if dev is None:
            return {"device_ops": [], "idle_gaps": []}
        by_name: Dict[str, float] = {}
        for o in self.ops[dev]:
            for a, b in self._clip([o]):
                by_name[o.name] = by_name.get(o.name, 0.0) + (b - a)
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for a, b in idle_intervals(self._clip(self.ops[dev]), self.window):
            gaps.append([self.host_at(0.5 * (a + b)), b - a])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": gaps[:top]}

    def host_at(self, t: float) -> str:
        inner = None
        for name, a, b in self.spans:
            if name != "window" and a <= t <= b and (
                    inner is None or b - a < inner[2] - inner[1]):
                inner = (name, a, b)
        return inner[0] if inner else "window"


def union_length(intervals: Sequence[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def idle_intervals(intervals: Sequence[Interval], window: Interval
                   ) -> List[Interval]:
    gaps, end = [], window[0]
    for a, b in sorted(intervals):
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if window[1] > end:
        gaps.append((end, window[1]))
    return gaps


def _stat(event, key: str) -> Optional[str]:
    for k, v in event.stats:
        if k == key:
            return v
    return None


def read_xplane(path: Path) -> Trace:
    """Reduce one ``.xplane.pb`` to the window, the device operations and
    the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: Dict[int, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted((e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name)
                          for e in lines.get(MODULES_LINE, []))
            starts = [a for a, _, _ in runs]
            dev_ops = ops.setdefault(int(m.group(1)), [])
            for e in lines.get(OPS_LINE, []):
                start = e.start_ns * 1e-9
                module = _stat(e, "hlo_module")
                if module is None:
                    i = bisect.bisect_right(starts, start) - 1
                    if i >= 0 and start <= runs[i][1]:
                        module = runs[i][2]
                dev_ops.append(Op(e.name, str(module), start,
                                  start + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        start = e.start_ns * 1e-9
                        spans.append((e.name, start,
                                      start + e.duration_ns * 1e-9))
    windows = [(a, b) for n, a, b in spans if n == "window"]
    if not windows:
        raise ValueError(f"{path}: no 'window' span in the trace")
    return Trace(window=max(windows, key=lambda w: w[1] - w[0]), ops=ops,
                 spans=spans)


class Tracer:
    """``jax.profiler`` around the window, written under ``out`` (listed in
    ``bench/.gitignore``) and reduced once the window has closed."""

    def __init__(self, out: Path):
        self.out = Path(out)

    def start(self):
        import jax
        shutil.rmtree(self.out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.out), profiler_options=opts)

    def stop(self) -> Trace:
        import jax
        jax.profiler.stop_trace()
        paths = sorted(self.out.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {self.out}")
        return read_xplane(paths[-1])
