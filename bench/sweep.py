"""Find a serving cell's knee: the highest offered rate with no growing
backlog and a p99 under the limit, by a sweep in one process.

    python3 bench/sweep.py --workload <cell> --rates 500,1000,2000 [--seconds 5]

Set-up once as a benchmark run does, then one window per rate; prints one
JSON line per rate with the p99, the completed rate and the generator's
lateness. The knee found is written into the cell's traffic file as a
number; the benchmark never searches for a rate itself.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.run import bootstrap  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    bootstrap()
    from bench.lib import harness
    _, _, cfg, traffic, runner, devices = harness.open_cell(args.workload)
    run = runner(cfg, traffic, args.seed, devices)
    run.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        run.traffic = dict(traffic, rate_per_s=rate)
        out = run.window(args.seconds)
        print(json.dumps({"rate_per_s": rate, **out["metrics"],
                          "failed": out["failed"], **run.counters}),
              flush=True)
    run.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
