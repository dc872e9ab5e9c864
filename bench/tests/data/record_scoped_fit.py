"""Record ``scoped_fit.xplane.pb``, the small trace the benchmark's trace
tests read: one two-round ``gal.fit`` of four ridge organizations (4,096
rows x 16 features, the linear cell's model, loss and eta method), run on
a TPU under ``jax.profiler`` inside the benchmark's ``window`` and ``fit``
spans, with the program's own spans and round-phase scopes. A first fit
outside the trace warms the small programs; the round program is built
again on every call, so the traced fit's ``gal.launch`` holds its tracing,
lowering and compile.

    python3 bench/tests/data/record_scoped_fit.py <out dir>

Prints the trace's summary (``bench.lib.program_trace``) and the path
written. Exits non-zero without a TPU.
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]


def main(out: Path) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    from bench.lib import program_trace, tabular
    from repro.core import gal
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.models import zoo

    key = tabular.seed_key(20261017)
    xs, y, _, _ = tabular.make_data(key, n=4096, d=16, m=4, n_test=8)
    config = gal.GALConfig(rounds=2, engine="scan")

    def fit():
        orgs = make_orgs(list(xs), zoo.Linear(ridge=1e-3))
        with jax.profiler.TraceAnnotation("fit"):
            return gal.fit(jax.random.fold_in(key, 1), orgs, y,
                           get_loss("mse"), config)

    jax.block_until_ready(fit().stacked_params)
    trace_dir = out / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        jax.block_until_ready(fit().stacked_params)
    jax.profiler.stop_trace()
    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    target = out / "scoped_fit.xplane.pb"
    shutil.copy(path, target)
    print(program_trace.summary(target))
    print(target, target.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
