"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` (see ``bench/lib/harness.py``). The run makes its inputs
from the seed, warms every shape its traffic uses (set-up, reported as
``setup_s``), measures for ``--seconds``, then checks what the measured
work produced against a plain reference. With ``--trace 1`` the window runs
under the profiler and the result carries the per-layer metrics instead of
the end-to-end ones.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def bootstrap() -> None:
    """The program and the benchmark on the path, and the environment set
    before JAX loads; every command of the benchmark starts here."""
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import harness
    harness.prepare_env()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, require_tpu: bool = True) -> int:
    from bench.lib import harness
    opened = harness.open_cell(args.workload, require_tpu)
    spec, cell, cfg, traffic, runner, devices = opened
    import jax
    from bench.lib import trace as trace_lib
    events = harness.CompileEvents()
    run = runner(cfg, traffic, args.seed, devices)
    run.setup()
    setup_s = harness.process_age_s()
    setup_events = events.snapshot()
    harness.log(f"setup_s {setup_s:.3f}: {setup_events}")

    tracer = trace_lib.Tracer(harness.OUT_DIR / f"trace-{cell['name']}") \
        if args.trace else None
    mark = events.snapshot()
    if tracer:
        tracer.start()
    with jax.profiler.TraceAnnotation("window"):
        measured = run.window(args.seconds)
    trace = tracer.stop() if tracer else None
    in_window = events.since(mark)
    harness.log(f"window: {measured['attempted']} attempted, "
                f"{measured['failed']} failed; backend compiles or cache "
                f"loads {in_window['backend_compiles']}, cache hits "
                f"{in_window['cache_hits']}, misses "
                f"{in_window['cache_misses']}")
    peak = harness.memory_peak_bytes(devices)
    run.release()
    gc.collect()
    checks = run.check()

    result = {"correct": harness.checks_ok(checks),
              "attempted": measured["attempted"],
              "failed": measured["failed"]}
    if args.trace:
        ctx = {"cell": cell, "config": cfg, "traffic": traffic,
               "counters": run.counters, "events": in_window,
               "trace": trace, "peaks": harness.load_peaks(
                   devices[0].device_kind)}
        metrics = {}
        for m in harness.cell_metrics(spec, cell["name"], "per_layer"):
            value = harness.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        for m in harness.cell_metrics(spec, cell["name"], "end_to_end"):
            value = (setup_s if m["name"] == "setup_s"
                     else measured["metrics"].get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = harness.device_info(devices, peak)
    if trace is not None:
        result["device"].update(busy_s=trace.busy_s, window_s=trace.window_s)
        result["breakdown"] = trace.breakdown()
    harness.emit(result, checks)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    bootstrap()
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
