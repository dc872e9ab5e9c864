"""Readings of a cell's comparison for the program and for its control, on
several seeds in one process, to set the cell's limits from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 2]
    python3 bench/control.py --workload <cell> --seeds 11,12,13 --faults

For each seed: set-up as a benchmark run does, a short window at the cell's
own load (``--seconds``), then the readings of the program against the
plain reference, and the readings of the control (the reference computed
one precision step lower, put in the program's place). Prints one JSON line
per seed. With ``--faults``, the readings of the program with each fault of
``bench/tests/test_bench_faults.py`` planted in turn, one line per fault
and seed. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.run import bootstrap  # noqa: E402


def readings(workload: str, seeds, seconds: float, require_tpu: bool = True):
    from bench.lib import harness
    _, _, cfg, traffic, runner, devices = harness.open_cell(workload,
                                                            require_tpu)
    out = []
    for seed in seeds:
        run = runner(cfg, traffic, seed, devices)
        run.setup()
        run.window(seconds)
        run.release()
        gc.collect()
        t0 = time.perf_counter()
        program = {c["name"]: c["value"] for c in run.check()}
        ref_s = time.perf_counter() - t0
        line = {"seed": seed, "program": program, "control": run.control(),
                "reference_s": ref_s}
        out.append(line)
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
    return out


def fault_readings(workload: str, seeds, seconds: float,
                   require_tpu: bool = True):
    """The program's readings with each planted fault of the cell."""
    import importlib.util
    import pytest
    from bench.lib import harness
    path = harness.BENCH / "tests" / "test_bench_faults.py"
    mod_spec = importlib.util.spec_from_file_location("bench_faults", path)
    faults = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(faults)
    _, _, cfg, traffic, runner, devices = harness.open_cell(workload,
                                                            require_tpu)
    out = []
    for fault in faults.CASES[workload]:
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            for seed in seeds:
                run = runner(cfg, traffic, seed, devices)
                try:
                    run.setup()
                    run.window(seconds)
                    run.release()
                    gc.collect()
                    got = {c["name"]: c["value"] for c in run.check()}
                except Exception as e:       # a crash fails the fault
                    got = {"error": repr(e)[:300]}
                line = {"fault": fault.__name__, "seed": seed,
                        "readings": got}
                out.append(line)
                print(json.dumps(line), flush=True)
                del run
                gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    bootstrap()
    read = fault_readings if args.faults else readings
    read(args.workload, [int(s) for s in args.seeds.split(",")],
         args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
