"""Smoke run of the GAL main path on a TPU, through the entry points a user
calls. Weights and data are random, made from ``--seed``.

Default (one chip):
  phase A  tabular collaborations, M=8 organizations on a vertical split of
           ``data/synthetic.make_regression``: ``gal.fit`` ->
           ``save_artifact`` -> ``ArtifactRegistry`` / ``GALService``. Once
           homogeneous (the scan engine, millions of rows) and once as the
           paper's GB-SVM model mix (the grouped engine). Served rows must
           equal the in-memory ``result.predict`` bitwise, and each compiled
           engine must agree with the ``engine="python"`` oracle on a
           4096-row subset.
  phase B  LM-scale GAL, ``gal_lm.fit_lm(..., use_kernel=True)``: two
           stablelm-1.6b organizations at the published widths, depth cut
           to 2 layers. The residual kernel must lower for the chip
           (``tpu_custom_call``) and agree with ``kernels/ref.py``, and the
           training cross entropy must fall every round.

``--four-chips`` runs only the org-sharded engine (M=4 one org per chip,
M=16 a block of four per chip) against the scan engine on the same data.

Each phase prints its compile seconds (XLA compile or persistent-cache
load, from JAX's own monitoring events), steady seconds (wall time of a
second, identical call, less its compile time), the device's
``peak_bytes_in_use`` so far, and its checks. Any failed check raises, so
the script exits non-zero; it also exits non-zero, printing no result,
when JAX finds no TPU. On success the last line of standard output is one
JSON object naming the device.

Run: python chip_smoke.py [--four-chips] [--seed N]
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# ---- sizes ------------------------------------------------------------------
# phase A, homogeneous: M orgs x D features over N rows. The org slices and
# the engine's (M, N, D/M) f32 stack take 8.7 GB of the 16 GB of HBM; the
# compiled fit needs 5.1 GB beside the slices (memory_analysis of the v5e
# compile).
TAB_N = 1 << 23
TAB_D = 128
TAB_M = 8
TAB_ROUNDS = 10
# phase A, GB-SVM mix: the SVM stand-in (zoo.KernelRidge) solves an exact
# N x N kernel system per org. At N=6144 the v5e compiler refuses the batched
# LU of the four SVM orgs (scoped VMEM); N=4096 compiles, 5.9 GB.
MIX_N = 4096
ORACLE_N = 4096           # rows of the python-oracle comparison
SERVE_BATCH = 256         # service bucket; every request fills one bucket
SERVE_REQUESTS = 4
# phase B: stablelm-1.6b at its published widths, depth and tokens cut
LM_ARCH = "stablelm-1.6b"
LM_LAYERS = 2
LM_BATCH, LM_SEQ = 1, 512
# Two orgs' params, Adam moments and per-round snapshots do not fit at the
# full vocab (28.2 GB) or at 1/2 (17.0 GB); 1/3 needs 14.0 GB.
LM_VOCAB_DIV = 3          # vocab slice; never below 1/8 of the vocab
LM_ROUNDS, LM_LOCAL_STEPS = 2, 2
# --four-chips
SHARD_N = 1 << 18
SHARD_D = 64
SHARD_ROUNDS = 4

# Tolerances, each with its reason.
# compiled engine vs the python oracle, under "highest" matmul precision:
# the engines run the same draws in the same order; only f32 association
# differs between one vmapped program and the per-org loop, and the 100-epoch
# Adam weight fit and the L-BFGS eta search carry that into the 4th digit
# (the same bound tests/test_conformance.py holds the engines to on the CPU).
ORACLE_RTOL = ORACLE_ATOL = 1e-3
# shard vs scan: the same single-group program with the org axis as a device
# mesh instead of a vmap; psum and all-gather reassociate f32 sums
# (tests/test_shard_parity.py's bound for etas and weights).
SHARD_RTOL = SHARD_ATOL = 1e-3
# Pallas residual vs the jnp reference: both compute exp(x - max)/sum in f32;
# only the streamed (two-pass, tiled) summation order differs.
RESID_ATOL = 1e-5


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"  check ok: {what}", flush=True)


class CompileClock:
    """Seconds JAX spends in XLA compilation (or loading a compiled program
    from the persistent cache), summed from its monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def run_twice(clock, fn):
    """Call ``fn`` cold, then again warm. Returns the warm result and
    (compile seconds of the cold call, steady seconds of the warm call)."""
    c0 = clock.seconds
    fn()
    compile_s = clock.seconds - c0
    c1, t1 = clock.seconds, time.perf_counter()
    out = fn()
    steady_s = time.perf_counter() - t1 - (clock.seconds - c1)
    return out, compile_s, steady_s


def report(name, compile_s, steady_s) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase {name}: compile_s={compile_s:.3f} steady_s={steady_s:.3f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)


# ---- phase A ----------------------------------------------------------------

def tabular_phase(clock, seed: int, name: str, n: int, d: int, m: int,
                  models, engine: str) -> None:
    import jax
    from repro.checkpoint import save_artifact
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    from repro.data.synthetic import make_regression
    from repro.serve import ArtifactRegistry, GALService

    rng = np.random.default_rng(seed)
    ds = make_regression(rng, n=n, d=d)
    xs, y = split_features(ds.x, m), ds.y
    del ds                                  # one copy of the features
    # requests are rows of the fitted collaboration's own orgs
    xs_req = [np.asarray(x[:SERVE_BATCH * SERVE_REQUESTS]) for x in xs]
    loss = get_loss("mse")
    key = jax.random.PRNGKey(seed)
    cfg = GALConfig(rounds=TAB_ROUNDS)
    stack_bytes = sum(int(x.size) * x.dtype.itemsize for x in xs)
    print(f"phase {name}: M={m} N={n} D={d} T={TAB_ROUNDS} "
          f"org_stack_bytes={stack_bytes}", flush=True)

    result, compile_s, steady_s = run_twice(
        clock, lambda: gal.fit(key, make_orgs(xs, models()), y, loss, cfg))
    report(f"{name} fit", compile_s, steady_s)
    check(result.engine == engine, f"engine == {engine!r} ({result.engine})")
    check(bool(np.all(np.isfinite(result.etas)))
          and result.rounds == TAB_ROUNDS, f"{TAB_ROUNDS} finite etas")
    losses = result.history["train_loss"]
    check(losses[-1] < losses[0],
          f"train loss falls {losses[0]:.6g} -> {losses[-1]:.6g}")

    # fit once -> artifact -> registry -> service, bucket-sized requests
    with tempfile.TemporaryDirectory(prefix=".chip_smoke-", dir=ROOT) as tmp:
        path = save_artifact(result, Path(tmp) / name)
        registry = ArtifactRegistry(max_batch=SERVE_BATCH)
        registry.register(name, path)
        service = GALService(registry)
        t0 = time.perf_counter()
        c0 = clock.seconds
        service.warmup(name)
        reqs = [[x[i * SERVE_BATCH:(i + 1) * SERVE_BATCH] for x in xs_req]
                for i in range(SERVE_REQUESTS)]
        futures = [service.submit(name, r) for r in reqs]
        served = [np.asarray(f.result(timeout=600)) for f in futures]
        serve_s = time.perf_counter() - t0
        service.close()
        report(f"{name} serve", clock.seconds - c0,
               serve_s - (clock.seconds - c0))
    predict = jax.jit(result.predict)
    for i, (req, got) in enumerate(zip(reqs, served)):
        want = np.asarray(predict(req))
        check(got.shape == want.shape and np.array_equal(got, want),
              f"request {i}: {got.shape[0]} served rows == result.predict "
              f"bitwise")

    # the compiled engine against the python oracle on a subset
    sub = [x[:ORACLE_N] for x in xs]
    with jax.default_matmul_precision("highest"):
        fast = gal.fit(key, make_orgs(sub, models()), y[:ORACLE_N], loss, cfg)
        c0, t0 = clock.seconds, time.perf_counter()
        ref = gal.fit(key, make_orgs(sub, models()), y[:ORACLE_N], loss,
                      replace(cfg, engine="python"))
        report(f"{name} python oracle", clock.seconds - c0,
               time.perf_counter() - t0)
        req = [jax.numpy.asarray(x[:SERVE_BATCH]) for x in xs_req]
        p_fast = np.asarray(fast.predict(req))
        p_ref = np.asarray(ref.predict(req))
    check(fast.engine == engine and ref.engine == "python",
          f"oracle engines {fast.engine}/{ref.engine}")
    for what, a, b in (("etas", fast.etas, ref.etas),
                       ("weights", np.stack(fast.weights),
                        np.stack(ref.weights)),
                       ("predictions", p_fast, p_ref)):
        err = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        check(np.allclose(a, b, rtol=ORACLE_RTOL, atol=ORACLE_ATOL),
              f"{engine} vs python {what} within rtol=atol={ORACLE_RTOL} "
              f"(max abs diff {err:.3g})")


def gb_svm_models(m: int):
    from repro.models.zoo import KernelRidge, StumpBoost
    return lambda: [StumpBoost(n_stumps=20) if i % 2 == 0 else KernelRidge()
                    for i in range(m)]


def linear_models():
    from repro.models.zoo import Linear
    return Linear


# ---- phase B ----------------------------------------------------------------

def lm_phase(clock, seed: int, cfg) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import gal_lm
    from repro.data.tokens import make_token_stream, token_batches
    from repro.kernels import ref

    rng = np.random.default_rng(seed)
    stream = make_token_stream(rng, cfg.vocab, 4 * LM_BATCH * LM_SEQ + 2)
    toks, labels = next(token_batches(stream, LM_BATCH, LM_SEQ, rng))
    toks, labels = jnp.asarray(toks), jnp.asarray(labels)
    root = int(np.sqrt(cfg.vocab))
    views = (lambda t: (t // root) % cfg.vocab,     # high digits of the token
             lambda t: (t % root) % cfg.vocab)      # low digits
    key = jax.random.PRNGKey(seed)

    def fit():
        orgs = [gal_lm.LMOrganization(i, cfg, views[i]) for i in range(2)]
        for i, org in enumerate(orgs):
            org.init(jax.random.fold_in(key, i), lr=1e-3)
        return gal_lm.fit_lm(key, orgs, toks, labels, rounds=LM_ROUNDS,
                             local_steps=LM_LOCAL_STEPS, use_kernel=True)

    result, compile_s, steady_s = run_twice(clock, fit)
    report("B fit_lm", compile_s, steady_s)
    check(result.engine == "scan", f"engine == 'scan' ({result.engine})")
    xent = result.history["train_xent"]
    check(all(b < a for a, b in zip(xent, xent[1:])),
          f"train_xent falls every round {[round(v, 6) for v in xent]}")

    f = result.resume_state["f"].reshape(LM_BATCH, LM_SEQ, cfg.vocab)
    resid = jax.jit(lambda lab, ff: gal_lm.compute_residual(
        lab, ff, use_kernel=True))
    text = resid.lower(labels, f).compile().as_text()
    check("tpu_custom_call" in text,
          "the residual path fit_lm runs compiles to a tpu_custom_call")
    got = np.asarray(resid(labels, f)).reshape(LM_BATCH * LM_SEQ, cfg.vocab)
    want = np.asarray(ref.residual_xent_ref(
        f.reshape(LM_BATCH * LM_SEQ, cfg.vocab), labels.reshape(-1)))
    err = float(np.max(np.abs(got - want)))
    check(err <= RESID_ATOL,
          f"kernel residual == residual_xent_ref within {RESID_ATOL} "
          f"(max abs diff {err:.3g})")


def lm_config():
    from repro.configs import get_arch
    full = get_arch(LM_ARCH)
    if LM_VOCAB_DIV > 8:
        raise ValueError("the vocab is never cut below 1/8")
    cfg = replace(full, n_layers=LM_LAYERS,
                  vocab=full.vocab // LM_VOCAB_DIV)
    print(f"phase B: {LM_ARCH} d_model={cfg.d_model} n_heads={cfg.n_heads} "
          f"d_ff={cfg.d_ff} (published widths)", flush=True)
    print(f"  cut: layers {full.n_layers} -> {cfg.n_layers}", flush=True)
    print(f"  cut: tokens per round {LM_BATCH}x{LM_SEQ}", flush=True)
    if cfg.vocab != full.vocab:
        print(f"  cut: vocab {full.vocab} -> {cfg.vocab} "
              f"(1/{LM_VOCAB_DIV}, to fit 16 GB)", flush=True)
    return cfg


# ---- --four-chips -----------------------------------------------------------

def shard_phase(clock, seed: int, m: int) -> None:
    import jax
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    from repro.data.synthetic import make_regression
    from repro.models.zoo import Linear

    rng = np.random.default_rng(seed)
    ds = make_regression(rng, n=SHARD_N + SERVE_BATCH, d=SHARD_D)
    xs_all = split_features(ds.x, m)
    xs, req = [x[:SHARD_N] for x in xs_all], [x[SHARD_N:] for x in xs_all]
    y = ds.y[:SHARD_N]
    loss = get_loss("mse")
    key = jax.random.PRNGKey(seed)
    placement = "one org per chip" if m <= len(jax.devices()) else (
        f"{m // len(jax.devices())} orgs per chip")
    print(f"phase shard M={m} ({placement}): N={SHARD_N} D={SHARD_D} T={SHARD_ROUNDS}",
          flush=True)
    with jax.default_matmul_precision("highest"):
        res, compile_s, steady_s = run_twice(clock, lambda: gal.fit(
            key, make_orgs(xs, Linear()), y, loss,
            GALConfig(rounds=SHARD_ROUNDS, engine="shard")))
        report(f"shard M={m}", compile_s, steady_s)
        base = gal.fit(key, make_orgs(xs, Linear()), y, loss,
                       GALConfig(rounds=SHARD_ROUNDS, engine="scan"))
        p_sh, p_sc = np.asarray(res.predict(req)), np.asarray(
            base.predict(req))
    check(res.engine == "shard" and base.engine == "scan",
          f"engines {res.engine}/{base.engine}")
    devices = {dev for leaf in jax.tree_util.tree_leaves(res.stacked_params)
               for dev in leaf.sharding.device_set}
    check(len(devices) == 4 and all(dv.platform == "tpu" for dv in devices),
          f"org-stacked params on {len(devices)} distinct TPU devices")
    for what, a, b in (("etas", res.etas, base.etas),
                       ("weights", np.stack(res.weights),
                        np.stack(base.weights)),
                       ("predictions", p_sh, p_sc)):
        err = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        check(np.allclose(a, b, rtol=SHARD_RTOL, atol=SHARD_ATOL),
              f"shard vs scan {what} within rtol=atol={SHARD_RTOL} "
              f"(max abs diff {err:.3g})")


# ---- entry point ------------------------------------------------------------

def main() -> int:
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the org-sharded engine on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    want = 4 if args.four_chips else 1
    if count < want:
        print(f"chip_smoke: needs {want} TPU chip(s), found {count}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{count}; compile cache: {cache}",
          flush=True)
    clock = CompileClock()
    t_start = time.perf_counter()
    if args.four_chips:
        for m in (4, 16):
            shard_phase(clock, args.seed, m)
    else:
        tabular_phase(clock, args.seed, "A-scan", TAB_N, TAB_D, TAB_M,
                      linear_models(), "scan")
        tabular_phase(clock, args.seed, "A-grouped", MIX_N, TAB_D, TAB_M,
                      gb_svm_models(TAB_M), "grouped")
        lm_phase(clock, args.seed, lm_config())
    print(f"total_s={time.perf_counter() - t_start:.3f} "
          f"compile_s={clock.seconds:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
