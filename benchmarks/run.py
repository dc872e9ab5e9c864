"""Benchmark harness: one function per paper table/figure + microbenchmarks.

CSV format: ``name,us_per_call,derived`` for timing rows; table rows are
``table,setting,metric,value,check``. Roofline numbers come from the dry-run
artifacts (benchmarks/results/dryrun) and are summarized at the end.

Run: PYTHONPATH=src python -m benchmarks.run [--only tableN]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.utils.compile_cache import enable_compile_cache


def _time_call(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def micro_benchmarks() -> None:
    """Kernel + protocol micro-timings (CPU interpret mode — relative only)."""
    from repro.kernels.ops import residual_xent
    from repro.kernels import ref
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (512, 4096))
    labels = jax.random.randint(key, (512,), 0, 4096)
    t_ref = _time_call(jax.jit(ref.residual_xent_ref), logits, labels)
    print(f"residual_xent_ref_512x4096,{t_ref:.1f},jnp-oracle")
    from repro.core.weights import fit_weights
    from repro.core.losses import lq_loss
    r = jax.random.normal(key, (1024, 8))
    preds = jax.random.normal(key, (8, 1024, 8))
    t_w = _time_call(
        lambda: fit_weights(key, r, preds, lq_loss(2.0), epochs=100))
    print(f"assistance_weights_fit_M8,{t_w:.1f},adam-100-epochs")
    from repro.optim.lbfgs import line_search
    t_ls = _time_call(
        lambda: line_search(lambda e: jnp.mean((e - 1.7) ** 2), "lbfgs"))
    print(f"eta_line_search_lbfgs,{t_ls:.1f},scalar")


def _bench_smooth_l1(r, f):
    """A custom (non-ell_q) local loss: exercises the autodiff-residual
    compile path in the engine benchmark's mixed scenario."""
    import jax.numpy as jnp
    return jnp.mean(jnp.sqrt(1.0 + jnp.square(r - f)) - 1.0)


def gal_engine_benchmark(rounds: int = 16, m: int = 4, n: int = 512,
                         d: int = 16, json_rows: list | None = None) -> None:
    """rounds/sec of gal.fit per engine and scenario — homogeneous Linear,
    the paper's GB–SVM-style mixed-model set (model autonomy, fused by the
    org execution planner), noisy orgs (Table 6), Deep Model Sharing
    (Sec. 5: the python loop retraces its growing residual stack every
    round; the grouped engine compiles the stacked-head carry ONCE), and
    the DMS + custom-loss mix — plus the stacked-round prediction stage vs
    the per-(round, org) loop. Timings include compilation — one fit call
    is the real unit of work. Rows are appended to ``json_rows`` for the
    BENCH_PR5.json artifact."""
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss, lq_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import pad_and_stack, split_features
    from repro.data.synthetic import make_regression, train_test_split
    from repro.models.zoo import KernelRidge, Linear, MLP, StumpBoost

    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    ds = make_regression(rng_np, n=n, d=d)
    train, test = train_test_split(ds, rng_np)
    xs = split_features(train.x, m)
    xs_te = split_features(test.x, m)
    loss = get_loss("mse")

    scenarios = {
        "homogeneous": dict(models=lambda: Linear(), sigmas=None,
                            engines=("python", "scan")),
        "hetero_gb_svm_mix": dict(
            models=lambda: [StumpBoost(n_stumps=20) if i % 2 == 0
                            else KernelRidge() for i in range(m)],
            sigmas=None, engines=("python", "grouped")),
        "noisy": dict(models=lambda: Linear(),
                      sigmas=[0.0 if i % 2 == 0 else 1.0 for i in range(m)],
                      engines=("python", "grouped")),
        "dms": dict(models=lambda: MLP((16,), epochs=20), sigmas=None,
                    dms=True, engines=("python", "grouped")),
        "dms_custom_loss_mix": dict(
            models=lambda: [MLP((16,), epochs=20) if i % 2 == 0
                            else Linear(epochs=20) for i in range(m)],
            sigmas=None,
            dms=[i % 2 == 0 for i in range(m)],
            losses=[lq_loss(2.0) if i % 2 == 0 else _bench_smooth_l1
                    for i in range(m)],
            engines=("python", "grouped")),
    }
    results = {}
    for scen, spec in scenarios.items():
        for engine in spec["engines"]:
            cfg = GALConfig(rounds=rounds, engine=engine)
            orgs = make_orgs(xs, spec["models"](),
                             local_losses=spec.get("losses"),
                             dms=spec.get("dms", False),
                             noise_sigmas=spec["sigmas"])
            t0 = time.perf_counter()
            res = gal.fit(key, orgs, train.y, loss, cfg)
            dt = time.perf_counter() - t0
            results[(scen, engine)] = res
            rps = rounds / dt
            print(f"gal_fit_{scen}_{engine}_R{rounds}_M{m},"
                  f"{dt / rounds * 1e6:.1f},rounds_per_sec={rps:.2f}")
            if json_rows is not None:
                json_rows.append({
                    "scenario": scen, "engine": res.engine,
                    "forced_engine": engine, "rounds": rounds, "orgs": m,
                    "n": n, "d": d, "seconds": dt, "rounds_per_sec": rps,
                })
    for scen in ("dms", "dms_custom_loss_mix"):
        dt_py = [r for r in (json_rows or []) if r.get("scenario") == scen
                 and r.get("forced_engine") == "python"]
        dt_gr = [r for r in (json_rows or []) if r.get("scenario") == scen
                 and r.get("forced_engine") == "grouped"]
        if dt_py and dt_gr:
            x = dt_gr[-1]["rounds_per_sec"] / dt_py[-1]["rounds_per_sec"]
            print(f"# {scen}: grouped {x:.1f}x python")

    res = results[("homogeneous", "scan")]
    t_pred = _time_call(jax.jit(lambda xq: res.predict(xq)), xs_te)
    print(f"gal_predict_stacked_R{rounds}_M{m},{t_pred:.1f},one-vmap")
    res.unpack_to_orgs()
    xe_stack, _ = pad_and_stack(xs_te, pad_to=res.pad_to)
    t_leg = _time_call(lambda: res.predict_legacy(list(xe_stack)))
    print(f"gal_predict_legacy_R{rounds}_M{m},{t_leg:.1f},per-round-org-loop")
    if json_rows is not None:
        json_rows.append({"scenario": "predict_stacked", "engine": "scan",
                          "rounds": rounds, "orgs": m,
                          "us_per_call": t_pred})
        json_rows.append({"scenario": "predict_legacy", "engine": "python",
                          "rounds": rounds, "orgs": m,
                          "us_per_call": t_leg})


def gal_artifact_benchmark(rounds: int = 8, m: int = 4, n: int = 512,
                           d: int = 16,
                           json_rows: list | None = None) -> None:
    """The fit-once/serve-forever gap: cold start (fit the ensemble, save
    the artifact) vs warm start (load the artifact, compile the predict
    path) vs steady-state request latency on the loaded artifact. The
    warm row is what a production restart pays INSTEAD of the cold fit —
    the artifact lifecycle's whole value proposition, tracked per PR in
    the BENCH_PR5.json CI artifact."""
    import tempfile

    from repro.checkpoint import load_artifact, save_artifact
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    from repro.data.synthetic import make_regression, train_test_split
    from repro.models.zoo import Linear

    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    ds = make_regression(rng_np, n=n, d=d)
    train, test = train_test_split(ds, rng_np)
    xs = split_features(train.x, m)
    xs_te = split_features(test.x, m)

    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        res = gal.fit(key, make_orgs(xs, Linear()), train.y,
                      get_loss("mse"), GALConfig(rounds=rounds))
        save_artifact(res, tmp)
        dt_cold = time.perf_counter() - t0
        print(f"gal_serve_cold_fit_R{rounds}_M{m},{dt_cold * 1e6:.1f},"
              f"fit+save_s={dt_cold:.2f}")

        t0 = time.perf_counter()
        art = load_artifact(tmp)
        serve = jax.jit(lambda xq: art.predict(xq))
        jax.block_until_ready(serve(xs_te))          # compile = warm-up
        dt_warm = time.perf_counter() - t0
        print(f"gal_serve_warm_load_R{rounds}_M{m},{dt_warm * 1e6:.1f},"
              f"load+compile_s={dt_warm:.2f};"
              f"cold_over_warm={dt_cold / max(dt_warm, 1e-9):.1f}x")

        t_req = _time_call(serve, xs_te)
        print(f"gal_serve_artifact_request_R{rounds}_M{m},{t_req:.1f},"
              f"jitted-predict-cached")
    if json_rows is not None:
        json_rows.append({"scenario": "serve_cold_fit", "engine": res.engine,
                          "rounds": rounds, "orgs": m, "seconds": dt_cold})
        json_rows.append({"scenario": "serve_warm_load", "engine": art.engine,
                          "rounds": rounds, "orgs": m, "seconds": dt_warm,
                          "cold_over_warm": dt_cold / max(dt_warm, 1e-9)})
        json_rows.append({"scenario": "serve_artifact_request",
                          "engine": art.engine, "rounds": rounds, "orgs": m,
                          "us_per_call": t_req})


def gal_membership_benchmark(rounds: int = 8, m: int = 4, n: int = 512,
                             d: int = 16,
                             json_rows: list | None = None) -> None:
    """Dynamic-membership cost rows for the BENCH artifact:

    * ``dropout_round_overhead`` — steady-state (post-compile) fit time
      with a dropout schedule vs the unmasked fit. Membership rides the
      scan inputs as a boolean row, so the masked program should cost
      within a few percent of the unmasked one; the ratio is recorded as
      DATA (CI tracks drift, the 5%% expectation is advisory here).
    * ``contrib_loo_refit`` — one leave-one-out counterfactual via resume
      from the round-``t0`` carry vs the same counterfactual fit from
      scratch: the speedup the contributivity estimators
      (``repro.core.contrib``) bank on."""
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    from repro.data.synthetic import make_regression, train_test_split
    from repro.models.zoo import Linear

    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    ds = make_regression(rng_np, n=n, d=d)
    train, _ = train_test_split(ds, rng_np)
    xs = split_features(train.x, m)
    loss = get_loss("mse")
    cfg = GALConfig(rounds=rounds, engine="scan")
    # the overhead row runs LONG (8x) so the scanned rounds — the thing
    # membership actually touches — are a visible fraction of the one-shot
    # fit; at toy sizes trace+compile dominates and is schedule-independent
    r_ov = 8 * rounds
    cfg_ov = GALConfig(rounds=r_ov, engine="scan")
    sched = np.ones((r_ov, m), bool)
    sched[1::2, m - 1] = False          # last org drops every other round

    def fit_once(membership=None, resume=None, config=cfg):
        return gal.fit(key, make_orgs(xs, Linear()), train.y, loss, config,
                       membership=membership, resume_from=resume)

    def best_of(fn, iters: int = 3) -> float:
        # each gal.fit call re-traces, so min-of-iters is the stable
        # number (first calls eat allocator/caching warm-up noise)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    fit_once()                           # process warm-up
    t_plain = best_of(lambda: fit_once(config=cfg_ov))
    t_masked = best_of(lambda: fit_once(membership=sched, config=cfg_ov))
    ratio = t_masked / max(t_plain, 1e-12)
    print(f"gal_fit_dropout_overhead_R{r_ov}_M{m},"
          f"{t_masked / r_ov * 1e6:.1f},masked_over_unmasked={ratio:.3f}")
    if json_rows is not None:
        json_rows.append({
            "scenario": "dropout_round_overhead", "engine": "scan",
            "rounds": r_ov, "orgs": m, "n": n, "d": d,
            "seconds_unmasked": t_plain, "seconds_masked": t_masked,
            "masked_over_unmasked": ratio, "within_5pct": ratio <= 1.05,
        })

    # LOO counterfactual: resume from the t0 carry vs fit from scratch
    t0_cut = rounds // 2
    base = gal.fit(key, make_orgs(xs, Linear()), train.y, loss,
                   GALConfig(rounds=t0_cut, engine="scan"))
    loo_sched = np.ones((rounds, m), bool)
    loo_sched[t0_cut:, 0] = False       # org 0 leaves at the cut

    t_resume = best_of(lambda: fit_once(membership=loo_sched, resume=base))
    t_scratch = best_of(lambda: fit_once(membership=loo_sched))
    speedup = t_scratch / max(t_resume, 1e-12)
    print(f"gal_contrib_loo_refit_R{rounds}_M{m},"
          f"{t_resume * 1e6:.1f},resume_speedup={speedup:.2f}x"
          f";rounds_executed={rounds - t0_cut}_vs_{rounds}")
    if json_rows is not None:
        json_rows.append({
            "scenario": "contrib_loo_refit", "engine": "scan",
            "rounds": rounds, "orgs": m, "t0": t0_cut,
            "rounds_executed_resume": rounds - t0_cut,
            "seconds_resume": t_resume, "seconds_scratch": t_scratch,
            "resume_speedup": speedup,
        })


_SHARD_CELL_SNIPPET = r"""
import json, time
from repro.utils.force_devices import apply_force_devices
apply_force_devices()
import numpy as np
import jax
from repro.core import gal
from repro.core.gal import GALConfig
from repro.core.losses import get_loss
from repro.core.organizations import make_orgs
from repro.data.partition import split_features
from repro.data.synthetic import make_regression, train_test_split
from repro.models.zoo import Linear

rounds, m, n, d = {rounds}, {m}, {n}, {d}
rng_np = np.random.default_rng(0)
key = jax.random.PRNGKey(0)
ds = make_regression(rng_np, n=int(n / 0.8) + 2, d=d)
train, _ = train_test_split(ds, rng_np)          # train split has n rows
xs = split_features(train.x, m)
t0 = time.perf_counter()
res = gal.fit(key, make_orgs(xs, Linear()), train.y, get_loss("mse"),
              GALConfig(rounds=rounds, engine="{engine}",
                        residual_dtype="{dtype}"))
dt = time.perf_counter() - t0
print("CELL:" + json.dumps({{
    "engine": res.engine, "devices": len(jax.devices()), "seconds": dt,
    "n": int(train.y.shape[0]),
    "bcast": sum(res.history["comm_broadcast_bytes"]),
    "gather": sum(res.history["comm_gather_bytes"]),
}}))
"""


def _run_shard_cell(n_dev: int, m: int, n: int, d: int, rounds: int,
                    engine: str, dtype: str, timeout: int = 900):
    """One cold subprocess fit (forced device count must be set before jax
    initializes, so every cell is its own process). Returns the CELL dict
    or an error string."""
    import os
    import subprocess
    import sys

    snippet = _SHARD_CELL_SNIPPET.format(rounds=rounds, m=m, n=n, d=d,
                                         engine=engine, dtype=dtype)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_FORCE_DEVICES": str(n_dev)}
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    try:
        proc = subprocess.run([sys.executable, "-c", snippet], env=env,
                              capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return f"timeout>{timeout}s"
    if proc.returncode != 0:
        return " ".join(proc.stderr.strip().splitlines()[-1:]) or "crashed"
    for line in proc.stdout.splitlines():
        if line.startswith("CELL:"):
            return json.loads(line[len("CELL:"):])
    return "no CELL line in output"


def gal_shard_scaling_benchmark(json_rows: list | None = None,
                                full: bool = False) -> None:
    """The PR8 placement grid: orgs x train rows x placement x wire dtype.

    Placements per org count M:
      * ``scan``       — the single-device baseline (vmap over orgs, D=1);
      * ``one_to_one`` — the classic org mesh, one org per device (D=M;
        skipped for M=64, where forcing 64 host devices on one machine
        times every cell against the scheduler instead of the engine);
      * ``block``      — MORE orgs than devices: D=8 forced devices carry
        M/8 orgs each (D=2 for M=4), the placement this PR adds.

    Timing is the MARGINAL round rate from a cold-process pair: each cell
    runs twice in fresh subprocesses at R and 3R rounds, and
    rounds/sec = 2R / (t_3R - t_R). Differencing two cold processes
    cancels the compile+trace time that dominates small cells; same-process
    re-timing does NOT work here (the warm second call reuses jit caches
    and the asymmetry swamps the signal). Fast cells escalate R (x4, x16)
    until the marginal clears the cold-start noise floor — a cell whose
    difference stays non-positive even then is reported failed rather
    than clamped to a fictitious rate. Comm bytes are the engine's own
    per-round ledger ints, so the bf16 rows document the halved broadcast
    next to their fp32 twins.

    The default grid is the CI smoke slice (n=512, M in {4, 16});
    ``full=True`` (the ``--full-shard-grid`` flag) runs the committed
    BENCH_PR8.json grid with n=65536 and M=64 cells — the block-vs-scan
    acceptance numbers live there."""
    grid_m = (4, 16, 64) if full else (4, 16)
    grid_n = (512, 65536) if full else (512,)
    base_r = 4
    # A cold-pair marginal below this is dominated by compile-time
    # variance between the two fresh processes, not by round cost.
    _MARGINAL_FLOOR_S = 0.4

    for n in grid_n:
        for m in grid_m:
            # wide-feature orgs at bench scale would time the local solve;
            # the big-n cells give each org one feature so the round loop
            # (broadcast, fits, weight fit, line search) is what scales
            d = 4 * m if n == 512 else m
            cells = [("scan", 1, "scan")]
            if m <= 16:
                cells.append(("one_to_one", m, "shard"))
            else:
                print(f"# skip one_to_one M={m} n={n}: would force {m} "
                      f"host devices on one machine")
            cells.append(("block", 2 if m == 4 else 8, "shard"))
            for placement, n_dev, engine in cells:
                for dtype in ("fp32", "bf16"):
                    # Fast cells put the 8-round marginal below the
                    # compile-time variance between two cold processes;
                    # escalate the round count until the difference
                    # clears the noise floor instead of clamping it.
                    for mult in (1, 4, 16):
                        r1, r3 = base_r * mult, 3 * base_r * mult
                        a = _run_shard_cell(n_dev, m, n, d, r1, engine,
                                            dtype)
                        b = _run_shard_cell(n_dev, m, n, d, r3, engine,
                                            dtype)
                        if not (isinstance(a, dict)
                                and isinstance(b, dict)):
                            break
                        marginal = b["seconds"] - a["seconds"]
                        if marginal >= _MARGINAL_FLOOR_S:
                            break
                    name = (f"gal_shard_{placement}_{dtype}_D{n_dev}"
                            f"_M{m}_N{n}")
                    if not (isinstance(a, dict) and isinstance(b, dict)):
                        print(f"{name},nan,failed={a if isinstance(a, str) else b}")
                        continue
                    if marginal <= 0:
                        print(f"{name},nan,"
                              f"failed=unstable_marginal_at_{r3}_rounds")
                        continue
                    rps = (r3 - r1) / marginal
                    print(f"{name},{marginal / (r3 - r1) * 1e6:.1f},"
                          f"rounds_per_sec={rps:.2f};engine={b['engine']};"
                          f"bcast_B_per_round={b['bcast'] // r3};"
                          f"gather_B_per_round={b['gather'] // r3}")
                    if json_rows is not None:
                        json_rows.append({
                            "scenario": "shard_scaling",
                            "placement": placement, "dtype": dtype,
                            "devices": n_dev, "engine": b["engine"],
                            "rounds": r3 - r1, "orgs": m,
                            "n": b.get("n", n), "d": d,
                            "seconds": marginal, "rounds_per_sec": rps,
                            "comm_broadcast_bytes_per_round":
                                b["bcast"] // r3,
                            "comm_gather_bytes_per_round":
                                b["gather"] // r3,
                        })


def gal_lm_kernel_benchmark(json_rows: list | None = None,
                            tokens: int = 128) -> None:
    """The assistance-gradient kernel at REAL zoo vocab sizes: the Pallas
    ``residual_xent`` (interpret mode on CPU — relative numbers only)
    against the jitted autodiff ``CrossEntropyLoss.residual`` oracle that
    any custom Loss compiles through, at zamba2-2.7b's 32000 and
    qwen3-1.7b's 151936. Each row records both timings AND the max
    absolute error, so the artifact is simultaneously a perf row and a
    numerics pin."""
    from repro.configs import get_arch
    from repro.core.losses import CrossEntropyLoss, autodiff_residual
    from repro.kernels.ops import residual_xent

    key = jax.random.PRNGKey(0)
    loss = CrossEntropyLoss()
    for arch in ("zamba2-2.7b", "qwen3-1.7b"):
        v = int(get_arch(arch).vocab)
        logits = jax.random.normal(key, (tokens, v)) * 3
        labels = jax.random.randint(key, (tokens,), 0, v)
        y = jax.nn.one_hot(labels, v, dtype=jnp.float32)
        t_kernel = _time_call(lambda: residual_xent(logits, labels),
                              iters=3)
        oracle = jax.jit(lambda yy, ff: autodiff_residual(loss, yy, ff))
        t_auto = _time_call(oracle, y, logits, iters=3)
        err = float(jnp.max(jnp.abs(residual_xent(logits, labels)
                                    - oracle(y, logits))))
        print(f"residual_xent_{arch}_V{v}_T{tokens},{t_kernel:.1f},"
              f"autodiff_us={t_auto:.1f};max_abs_err={err:.2e}")
        assert err < 1e-4, f"kernel drifted from autodiff oracle: {err}"
        if json_rows is not None:
            json_rows.append({
                "scenario": "residual_xent_kernel_vs_autodiff",
                "arch": arch, "vocab": v, "tokens": tokens,
                "us_per_call": t_kernel, "us_autodiff": t_auto,
                "max_abs_err": err,
                "kernel_mode": ("pallas" if jax.default_backend() == "tpu"
                                else "interpret"),
            })


def gal_lm_engine_benchmark(rounds: int = 2, local_steps: int = 2,
                            json_rows: list | None = None) -> None:
    """rounds/sec of ``fit_lm`` on the model-autonomy scenario — a
    transformer org + an RWKV org (two plan groups) — per engine. One fit
    call including compilation is the unit of work, matching the tabular
    engine rows."""
    import dataclasses

    from repro.configs import get_arch
    from repro.core import gal_lm

    def tiny(name):
        return dataclasses.replace(
            get_arch(name, smoke=True), n_layers=1, d_model=64, n_heads=2,
            n_kv_heads=2, d_ff=128, vocab=256)

    tfm_cfg, rwkv_cfg = tiny("llama3-8b"), tiny("rwkv6-7b")
    key = jax.random.PRNGKey(0)
    b, s = 2, 32
    tokens = jax.random.randint(key, (b, s), 0, 256)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0, 256)

    def make_orgs():
        orgs = [gal_lm.LMOrganization(0, tfm_cfg, lambda t: t),
                gal_lm.LMOrganization(1, rwkv_cfg,
                                      lambda t: jnp.flip(t, axis=-1))]
        for i, org in enumerate(orgs):
            org.init(jax.random.fold_in(key, 10 + i), lr=1e-3)
        return orgs

    for engine in ("python", "grouped"):
        t0 = time.perf_counter()
        res = gal_lm.fit_lm(key, make_orgs(), tokens, labels,
                            rounds=rounds, local_steps=local_steps,
                            engine=engine)
        dt = time.perf_counter() - t0
        rps = rounds / dt
        print(f"gal_lm_fit_mixed_{engine}_R{rounds},"
              f"{dt / rounds * 1e6:.1f},rounds_per_sec={rps:.2f};"
              f"groups={res.plan.n_groups}")
        if json_rows is not None:
            json_rows.append({
                "scenario": "lm_fit_mixed_arch", "engine": res.engine,
                "forced_engine": engine, "rounds": rounds,
                "orgs": 2, "groups": res.plan.n_groups,
                "batch": b, "seq": s, "seconds": dt,
                "rounds_per_sec": rps,
            })


_LM_TOKENS_SNIPPET = r"""
import json, time
from repro.utils.force_devices import apply_force_devices
apply_force_devices()
import jax
import jax.numpy as jnp
from repro.configs import get_arch
from repro.launch import sharding as shd
from repro.launch.mesh import make_device_mesh
from repro.models import pspec, transformer as tfm
from repro.train.steps import make_train_step

B, S, steps, model_shards = {batch}, {seq}, {steps}, {model_shards}
cfg = get_arch("llama3-8b", smoke=True)
mesh = make_device_mesh((1, model_shards), ("data", "model"))
pspec.set_mesh(mesh)                    # activates constrain() in tfm.apply
key = jax.random.PRNGKey(0)
abstract = jax.eval_shape(lambda k: tfm.init_params(k, cfg), key)
p_sh = shd.params_shardings(cfg, mesh, abstract)
params = jax.device_put(tfm.init_params(key, cfg), p_sh)
step, opt = make_train_step(cfg, "gal_residual", lr=1e-3, weight_decay=0.0)
o_sh = shd.opt_state_shardings(cfg, mesh, None, abstract)
opt_state = jax.device_put(opt.init(params), o_sh)
batch = {{
    "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
    "residual": jax.random.normal(
        jax.random.fold_in(key, 1), (B, S, cfg.vocab), jnp.float32) * 0.1,
}}
b_sh = shd.batch_shardings(
    cfg, mesh, {{k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in batch.items()}})
batch = {{k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}}
step = jax.jit(step)
params, opt_state, _ = step(params, opt_state, batch)   # compile + warm
jax.block_until_ready(params)
t0 = time.perf_counter()
for _ in range(steps):
    params, opt_state, metrics = step(params, opt_state, batch)
jax.block_until_ready(params)
dt = time.perf_counter() - t0
shardings = jax.tree_util.tree_leaves(
    p_sh, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding))
n_sharded = sum(1 for s in shardings
                if any(e is not None for e in s.spec))
print("CELL:" + json.dumps({{
    "devices": len(jax.devices()), "seconds": dt,
    "tokens_per_sec": B * S * steps / dt,
    "batch": B, "seq": S, "steps": steps, "arch": cfg.arch,
    "sharded_param_leaves": n_sharded, "param_leaves": len(shardings),
}}))
"""


def gal_lm_tokens_benchmark(json_rows: list | None = None,
                            model_shards: int = 4, batch: int = 4,
                            seq: int = 128, steps: int = 8,
                            timeout: int = 900) -> None:
    """tokens/sec of the sharded residual-fit train step — the first
    consumer of the model-parallel specs in ``launch/sharding.py`` /
    ``models/pspec.py``. A cold subprocess forces a (1, model_shards)
    ("data", "model") mesh (REPRO_FORCE_DEVICES must be set before jax
    initializes), places llama3-8b-smoke params/opt-state/batch with
    ``params_shardings`` / ``opt_state_shardings`` / ``batch_shardings``,
    installs the mesh via ``pspec.set_mesh`` so the activation constraints
    in ``tfm.apply`` are live, and times post-compile ``gal_residual``
    steps. The row records how many parameter leaves actually sharded —
    a spec regression that silently replicates everything fails loudly in
    the artifact diff."""
    import os
    import subprocess
    import sys

    snippet = _LM_TOKENS_SNIPPET.format(batch=batch, seq=seq, steps=steps,
                                        model_shards=model_shards)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_FORCE_DEVICES": str(model_shards)}
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    try:
        proc = subprocess.run([sys.executable, "-c", snippet], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"gal_lm_tokens_per_sec_D{model_shards},nan,"
              f"failed=timeout>{timeout}s")
        return
    cell = None
    if proc.returncode == 0:
        for line in proc.stdout.splitlines():
            if line.startswith("CELL:"):
                cell = json.loads(line[len("CELL:"):])
    if cell is None:
        err = " ".join(proc.stderr.strip().splitlines()[-1:]) or "crashed"
        print(f"gal_lm_tokens_per_sec_D{model_shards},nan,failed={err}")
        return
    assert cell["sharded_param_leaves"] > 0, \
        "model-parallel specs sharded nothing"
    print(f"gal_lm_tokens_per_sec_D{cell['devices']}_B{batch}_S{seq},"
          f"{cell['seconds'] / steps * 1e6:.1f},"
          f"tokens_per_sec={cell['tokens_per_sec']:.0f};"
          f"sharded={cell['sharded_param_leaves']}/{cell['param_leaves']}")
    if json_rows is not None:
        json_rows.append({
            "scenario": "tokens_per_sec",
            "arch": cell["arch"], "devices": cell["devices"],
            "mesh": {"data": 1, "model": model_shards},
            "batch": batch, "seq": seq, "steps": steps,
            "seconds": cell["seconds"],
            "tokens_per_sec": cell["tokens_per_sec"],
            "sharded_param_leaves": cell["sharded_param_leaves"],
            "param_leaves": cell["param_leaves"],
        })


def roofline_summary(outdir: str = "benchmarks/results/dryrun") -> None:
    """Summarize the dry-run artifacts into the SS Roofline table."""
    rows = []
    for f in sorted(Path(outdir).glob("*.json")):
        r = json.loads(f.read_text())
        t = r["roofline"]
        rows.append((r["arch"], r["shape"], r["mesh"],
                     t["t_compute"], t["t_memory"], t["t_collective"],
                     r["dominant"], r.get("useful_flops_ratio"),
                     r["memory"]["peak_bytes_per_device"] / 2 ** 30))
    if not rows:
        print("roofline,none,run `python -m repro.launch.dryrun --all` first,0")
        return
    print("arch,shape,mesh,t_compute_s,t_memory_s,t_collective_s,"
          "dominant,useful_flops_ratio,peak_GiB")
    for row in rows:
        a, s, m, tc, tm, tl, dom, u, pk = row
        u = "" if u is None else f"{u:.2f}"
        print(f"{a},{s},{m},{tc:.4f},{tm:.4f},{tl:.4f},{dom},{u},{pk:.2f}")


def _git_sha() -> str | None:
    """Best-effort commit SHA of the repo the benchmark ran from."""
    import subprocess
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=Path(__file__).resolve().parent)
        return out.stdout.strip() if out.returncode == 0 else None
    except Exception:
        return None


def bench_provenance() -> dict:
    """The run's provenance header: enough to tell two BENCH_*.json apart
    without trusting the filename — device layout, library versions, the
    exact commit. Stamped into every artifact by ``write_bench_json``."""
    return {
        "device_count": jax.device_count(),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "numpy_version": np.__version__,
        "git_sha": _git_sha(),
    }


def write_bench_json(path: str, rows: list) -> None:
    """Emit the machine-readable benchmark artifact (the BENCH_PR<N>.json
    CI artifact): rounds/sec per engine and scenario — including the
    heterogeneous GB–SVM-mix, membership-overhead and contributivity
    rows — with a provenance header, so CI tracks the perf trajectory
    across PRs and every artifact says which commit/devices produced it."""
    payload = {
        "schema": "gal-bench/v1",
        **bench_provenance(),
        "rows": rows,
    }
    Path(path).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {path} ({len(rows)} rows)")


def load_bench_json(path: str) -> dict:
    """Load a BENCH_*.json artifact from ANY PR generation, backfilling
    provenance fields older writers never stamped (``jax_version`` /
    ``numpy_version`` / ``git_sha`` arrive as None on PR4/PR5-era files)
    so downstream comparisons can treat every artifact uniformly.

    Rows are schema-checked: every row must be an object naming its
    ``scenario``, and any timing fields present must be numeric. Problem
    sizes older shard_scaling writers left implicit (``n`` / ``d`` /
    ``seconds``) are backfilled as None so consumers can select on them
    without per-generation special cases."""
    payload = json.loads(Path(path).read_text())
    if payload.get("schema") != "gal-bench/v1":
        raise ValueError(f"{path}: not a gal-bench/v1 artifact "
                         f"(schema={payload.get('schema')!r})")
    for field in ("device_count", "backend", "jax_version", "numpy_version",
                  "git_sha"):
        payload.setdefault(field, None)
    payload.setdefault("rows", [])
    if not isinstance(payload["rows"], list):
        raise ValueError(f"{path}: 'rows' must be a list")
    for i, row in enumerate(payload["rows"]):
        if not isinstance(row, dict) or not isinstance(
                row.get("scenario"), str):
            raise ValueError(f"{path}: row {i} is not an object with a "
                             f"'scenario' string")
        for field in ("seconds", "rounds_per_sec", "us_per_call"):
            if field in row and not isinstance(row[field], (int, float)):
                raise ValueError(f"{path}: row {i} field {field!r} is "
                                 f"not numeric")
        for field in ("n", "d", "seconds"):
            row.setdefault(field, None)
    return payload


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single table (table1..table6, fig4, table14)")
    ap.add_argument("--skip-tables", action="store_true")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write the engine-benchmark rows as machine-"
                         "readable JSON with a provenance header (the "
                         "BENCH_PR<N>.json CI artifact)")
    ap.add_argument("--engines-only", action="store_true",
                    help="run only the GAL engine benchmarks (the fast "
                         "CI-artifact path): no tables, no micro, no "
                         "roofline")
    ap.add_argument("--lm-only", action="store_true",
                    help="run only the LM-scale benchmarks (residual_xent "
                         "kernel vs autodiff at zoo vocab sizes, the "
                         "mixed-architecture fit_lm engines, and the "
                         "sharded tokens/sec cell) — the BENCH_PR10.json "
                         "CI-artifact path")
    ap.add_argument("--full-shard-grid", action="store_true",
                    help="run the full placement grid (orgs up to 64, "
                         "65536-row cells) instead of the CI smoke slice "
                         "— the committed BENCH_PR8.json numbers")
    args = ap.parse_args()

    json_rows: list = []
    if args.lm_only:
        print("# residual_xent kernel vs autodiff oracle at zoo vocab "
              "sizes (name,us_per_call,derived)")
        gal_lm_kernel_benchmark(json_rows=json_rows)
        print("\n# fit_lm mixed-architecture engines "
              "(name,us_per_round,derived)")
        gal_lm_engine_benchmark(json_rows=json_rows)
        print("\n# sharded residual-fit train step "
              "(name,us_per_step,derived)")
        gal_lm_tokens_benchmark(json_rows=json_rows)
        if args.json_out:
            write_bench_json(args.json_out, json_rows)
        return
    if args.engines_only:
        print("# gal engine benchmarks (name,us_per_round,derived)")
        gal_engine_benchmark(json_rows=json_rows)
        print("\n# gal artifact lifecycle: cold fit vs warm load "
              "(name,us,derived)")
        gal_artifact_benchmark(json_rows=json_rows)
        print("\n# gal membership + contributivity "
              "(name,us,derived)")
        gal_membership_benchmark(json_rows=json_rows)
        print("\n# gal shard engine scaling")
        gal_shard_scaling_benchmark(json_rows=json_rows,
                                    full=args.full_shard_grid)
        if args.json_out:
            write_bench_json(args.json_out, json_rows)
        return

    from benchmarks.tables import ALL_TABLES
    print("table,setting,metric,value,check")
    results = {}
    if not args.skip_tables:
        todo = ([args.only] if args.only else list(ALL_TABLES))
        for name in todo:
            t0 = time.time()
            ok = ALL_TABLES[name]()
            results[name] = ok
            print(f"# {name}: {'PASS' if ok else 'FAIL'} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    print("\n# microbenchmarks: name,us_per_call,derived")
    micro_benchmarks()

    print("\n# gal engine: fused engines vs legacy python per scenario "
          "(name,us_per_round,derived)")
    gal_engine_benchmark(json_rows=json_rows)

    print("\n# gal artifact lifecycle: cold fit vs warm load "
          "(name,us,derived)")
    gal_artifact_benchmark(json_rows=json_rows)

    print("\n# gal membership + contributivity: dropout overhead and the "
          "LOO resume speedup (name,us,derived)")
    gal_membership_benchmark(json_rows=json_rows)

    print("\n# gal shard engine scaling: rounds/sec at forced host devices "
          "(name,us_per_round,derived)")
    gal_shard_scaling_benchmark(json_rows=json_rows,
                                full=args.full_shard_grid)

    print("\n# roofline table (from dry-run artifacts)")
    roofline_summary()

    if args.json_out:
        write_bench_json(args.json_out, json_rows)

    if results:
        n_pass = sum(results.values())
        print(f"\n# SUMMARY: {n_pass}/{len(results)} paper-claim checks PASS")
        if n_pass < len(results):
            raise SystemExit(1)


if __name__ == "__main__":
    main()
