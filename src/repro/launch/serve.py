"""Production serving launcher: the GAL Prediction Stage.

Two serving modes:

  * LM decode (default): batched single-token decode at one organization
    against a KV/state cache on a mesh.
  * ``--gal-ensemble``: the full multi-org Prediction Stage — fit a
    homogeneous GAL ensemble on a synthetic vertical split, then serve
    batched predictions through the stacked-round fast path (ONE vmap over
    rounds x orgs per request) and report latency vs the legacy
    per-(round, org) Python assembly. ``--engine shard`` fits on the
    org-sharded multi-device engine (one org per device along an "org"
    mesh axis) and reports its per-round communication ledger.
    ``--hetero`` switches to the paper's model-autonomy setting: a
    GB–SVM-style mixed-model org set fit on the grouped fused engine,
    printing the planner's per-group composition alongside the serve
    latency. ``--dms`` fits Deep Model Sharing organizations (paper
    Sec. 4.2/5: one shared extractor + T stacked heads per org) on the
    grouped engine and prints the model-memory ledger's Tx saving next to
    the fresh-fit baseline. ``--save DIR`` persists the fitted ensemble as
    a versioned artifact (``repro.checkpoint.save_artifact``) after the
    fit; ``--load DIR`` skips the fit entirely and serves the artifact —
    fit once, serve forever: the loaded ensemble's jitted predict path is
    compiled once and cached across every subsequent request.

Examples (CPU container):
  REPRO_FORCE_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --arch rwkv6-7b --smoke --mesh 2,4 --batch 8 --steps 16
  PYTHONPATH=src python -m repro.launch.serve --gal-ensemble \
      --rounds 8 --orgs 4 --batch 256 --steps 32
  REPRO_FORCE_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \
      --gal-ensemble --engine shard --rounds 8 --orgs 4 --batch 256
  PYTHONPATH=src python -m repro.launch.serve --gal-ensemble --hetero \
      --rounds 8 --orgs 4 --batch 256
  PYTHONPATH=src python -m repro.launch.serve --gal-ensemble \
      --rounds 8 --orgs 4 --save /tmp/gal-artifact          # fit once
  PYTHONPATH=src python -m repro.launch.serve --gal-ensemble \
      --orgs 4 --load /tmp/gal-artifact                     # serve forever
  PYTHONPATH=src python -m repro.launch.serve --service \
      --tenants 2 --clients 8 --requests 256               # the service

``--service`` runs the multi-tenant inference service (``repro.serve``,
docs/serving.md): an artifact registry of ``--tenants`` collaborations
served through per-tenant bucketed micro-batching, driven by
``--clients`` concurrent closed-loop clients, reporting batched
throughput/latency against the one-request-at-a-time baseline.

NOTE: the ``REPRO_FORCE_DEVICES`` shim below must run before the first jax
operation in the process (see repro/utils/force_devices.py), so it sits
ahead of every other import.
"""
from repro.utils.force_devices import apply_force_devices
apply_force_devices()

import argparse
import time

import jax
import jax.numpy as jnp

from repro.utils.compile_cache import enable_compile_cache


def measure_request_path(fn, steps: int):
    """Time a jitted request path two ways (all clocks monotonic):

    * **blocked latency** — block on every result before issuing the
      next request: the time ONE caller waits for its answer.
    * **pipelined throughput** — dispatch all ``steps`` requests and
      block once at the end: what the async dispatch pipeline sustains.

    The old serve loop dispatched asynchronously and blocked only on the
    final result but printed the number as "ms/req" — that is the
    throughput figure, NOT the latency a caller sees; this helper
    reports both, under their real names. Returns ``(latency_s,
    throughput_s)`` per request, or ``(None, None)`` when ``steps == 0``
    (compile-only runs measure nothing).
    """
    if steps <= 0:
        return None, None
    t0 = time.perf_counter()
    for _ in range(steps):
        jax.block_until_ready(fn())
    lat = (time.perf_counter() - t0) / steps
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = fn()
    jax.block_until_ready(out)
    thr = (time.perf_counter() - t0) / steps
    return lat, thr


def _fmt_ms(seconds) -> str:
    return "n/a (steps=0)" if seconds is None else f"{seconds * 1e3:.2f} ms"


def gal_ensemble_serve(args) -> None:
    """Serve the stacked-round GAL ensemble; print ms/request for the fused
    vmap path next to the legacy per-(round, org) loop. With
    ``--engine shard`` the fit runs org-sharded across devices and the
    per-round communication ledger is printed. ``--save`` persists the
    fitted ensemble as an artifact after the (cold) fit; ``--load`` serves
    a saved artifact with NO fit at all — the warm-start path a production
    deployment restarts on."""
    import numpy as np
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    from repro.data.synthetic import make_regression, train_test_split
    from repro.models.zoo import Linear

    from repro.models.zoo import KernelRidge, MLP, StumpBoost

    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    req_widths = None
    if args.load:
        from repro.checkpoint import load_artifact
        t0 = time.perf_counter()
        res = load_artifact(args.load)
        dt_load = time.perf_counter() - t0
        if res.plan is not None and res.plan.n_orgs != args.orgs:
            # the artifact knows its own org count — no need to re-type it
            print(f"gal-ensemble: the artifact was fit on "
                  f"{res.plan.n_orgs} organizations; serving those "
                  f"(--orgs {args.orgs} ignored)")
            args.orgs = res.plan.n_orgs
        if any(p is None for p in res.group_pads):
            raise SystemExit(
                "--load in this demo CLI serves tabular artifacts only "
                "(this one was fit on higher-rank slices); load it with "
                "repro.checkpoint.load_artifact and call predict directly")
        # request slices must reproduce the artifact's per-org widths, in
        # org order — the registry recovers them from the plan geometry
        from repro.serve import request_widths
        req_widths = request_widths(res)
        print(f"gal-ensemble WARM start: loaded {args.load} in "
              f"{dt_load * 1e3:.0f} ms (engine={res.engine} "
              f"rounds={res.rounds}, no refit — the artifact outlives "
              f"the fitting process; --rounds/--engine describe fits and "
              f"are ignored here)")

    d_total = 4 * args.orgs if req_widths is None else sum(req_widths)
    ds = make_regression(rng_np, n=512, d=d_total)
    train, test = train_test_split(ds, rng_np)

    if not args.load:
        xs = split_features(train.x, args.orgs)
        engine = args.engine
        dms = False
        if args.dms:
            # Deep Model Sharing (paper Sec. 4.2/5): one shared extractor +
            # T stacked heads per org, fused by the grouped engine's carry
            models, dms = MLP((16,), epochs=20), True
            if engine in ("scan", "shard"):
                engine = "grouped"  # the DMS carry is grouped territory
        elif args.hetero:
            # model autonomy (paper Sec. 4.2): alternate GB / SVM stand-ins
            # so the planner fuses a mixed-model set into one compiled loop
            models = [StumpBoost(n_stumps=20) if i % 2 == 0
                      else KernelRidge() for i in range(args.orgs)]
            if engine in ("scan", "shard"):
                engine = "grouped"  # single-group engines cannot mix models
        else:
            models = Linear()
        t0 = time.perf_counter()
        orgs = make_orgs(xs, models, dms=dms)
        cfg = GALConfig(rounds=args.rounds, engine=engine)
        res = gal.fit(key, orgs, train.y, get_loss("mse"), cfg)
        dt_fit = time.perf_counter() - t0
        print(f"gal-ensemble COLD start: fit {args.rounds} rounds in "
              f"{dt_fit:.2f} s (engine={res.engine})")
        if args.contributions:
            from repro.core.contrib import leave_one_out, truncated_shapley
            cut = args.rounds // 2
            t0 = time.perf_counter()
            if args.contributions == "shapley":
                rep = truncated_shapley(key, orgs, train.y, get_loss("mse"),
                                        cfg, t0=cut, full=res)
            else:
                rep = leave_one_out(key, orgs, train.y, get_loss("mse"),
                                    cfg, t0=cut, full=res)
            dt_c = time.perf_counter() - t0
            print(f"gal-ensemble contributivity ({rep['method']}, "
                  f"value={rep['value']} over rounds {cut}..{args.rounds}, "
                  f"{rep['refits']} counterfactual refits resumed from the "
                  f"round-{cut} carry, {dt_c:.2f} s):")
            print(f"  v_full={rep['v_full']:.4f}  v_empty={rep['v_empty']:.4f}")
            for oid, s in zip(rep["org_ids"], rep["scores"]):
                bar = "#" * max(0, min(40, int(
                    40 * s / max(abs(max(rep["scores"], key=abs)), 1e-12))))
                print(f"  org {oid}: {s:+12.4f}  {bar}")
        if args.save:
            from repro.checkpoint import save_artifact
            t0 = time.perf_counter()
            save_artifact(res, args.save)
            print(f"gal-ensemble artifact saved to {args.save} in "
                  f"{(time.perf_counter() - t0) * 1e3:.0f} ms — serve it with "
                  f"--load {args.save} (no refit) or extend it with "
                  f"gal.fit(..., resume_from={args.save!r})")
    if "model_memories" in res.history:
        from repro.core.protocol_sim import gal_model_memories
        fresh = gal_model_memories(res.rounds, [False] * args.orgs)
        live = res.history["model_memories"][-1]
        dms = res.plan.has_dms if res.plan is not None else args.dms
        print(f"gal-ensemble model memories ({'DMS' if dms else 'fresh'}): "
              f"{live} live copies after {res.rounds} rounds "
              f"(fresh-fit baseline {fresh[-1]}; "
              f"{fresh[-1] / max(live, 1):.1f}x saving)")
    if res.plan is not None:
        sharded = (f", group stacks sharded over {res.mesh_devices} devices"
                   if res.mesh_devices else "")
        print(f"gal-ensemble plan ({res.engine}): "
              f"{res.plan.describe()}{sharded}")
    if "comm_broadcast_bytes" in res.history:
        tag = "collective" if res.engine == "shard" else "simulated"
        print(f"gal-ensemble comm ledger ({res.engine}, {tag}): "
              f"broadcast={sum(res.history['comm_broadcast_bytes']):.0f} B "
              f"gathered={sum(res.history['comm_gather_bytes']):.0f} B "
              f"over {res.rounds} rounds x {len(jax.devices())} devices")

    from repro.data.partition import split_channels
    slices = (split_channels(test.x, req_widths) if req_widths is not None
              else split_features(test.x, args.orgs))
    xs_req = [jnp.tile(x, (max(1, args.batch // x.shape[0]) + 1, 1)
                       )[:args.batch] for x in slices]
    # ONE jit compilation, cached across every subsequent request — for a
    # loaded artifact this is the entire warm-up cost of the deployment.
    # The compile call also BINDS the output, so --steps 0 still has a
    # result to verify against (the old loop left `out` unbound there).
    serve_fast = jax.jit(lambda xq: res.predict(xq))
    out = jax.block_until_ready(serve_fast(xs_req))       # compile
    lat_fast, thr_fast = measure_request_path(
        lambda: serve_fast(xs_req), args.steps)

    if args.load:
        # a loaded artifact has no live Organizations: the legacy
        # per-(round, org) loop does not apply — report the served path
        print(f"gal-ensemble orgs={args.orgs} rounds={res.rounds} "
              f"batch={args.batch}: stacked latency={_fmt_ms(lat_fast)}/req "
              f"pipelined={_fmt_ms(thr_fast)}/req "
              f"(warm-loaded artifact, jitted predict cached across "
              f"requests)")
        return

    res.unpack_to_orgs()                                  # legacy loop path
    # per-round params were fit at each GROUP's pad width: pad request
    # slices per group before the per-(round, org) assembly
    from repro.data.partition import stack_groups, unstack_groups
    index_groups = [g.indices for g in res.plan.groups]
    stacks, _, _ = stack_groups(xs_req, index_groups, pad_tos=res.group_pads)
    xs_padded = unstack_groups(stacks, index_groups)

    out_legacy = jax.block_until_ready(res.predict_legacy(xs_padded))
    lat_legacy, thr_legacy = measure_request_path(
        lambda: res.predict_legacy(xs_padded), args.steps)

    drift = float(jnp.max(jnp.abs(out - out_legacy)))
    speedup = ("n/a" if lat_fast is None
               else f"{lat_legacy / max(lat_fast, 1e-9):.1f}x")
    print(f"gal-ensemble orgs={args.orgs} rounds={args.rounds} "
          f"batch={args.batch}: "
          f"stacked latency={_fmt_ms(lat_fast)}/req "
          f"pipelined={_fmt_ms(thr_fast)}/req "
          f"legacy latency={_fmt_ms(lat_legacy)}/req "
          f"speedup={speedup} max_drift={drift:.2e}")


def service_serve(args) -> None:
    """``--service``: the multi-tenant inference service (docs/serving.md)
    under a concurrent closed-loop load harness. Registers ``--tenants``
    collaborations (fit fresh per-tenant, or ``--load DIR`` registered
    once per tenant), warms each tenant's bucket cache, then prints the
    batched service's throughput/latency next to the one-request-at-a-
    time baseline on the same artifacts."""
    import numpy as np
    from repro.core import gal
    from repro.core.gal import GALConfig
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    from repro.data.synthetic import make_regression, train_test_split
    from repro.models.zoo import Linear
    from repro.serve import (ArtifactRegistry, GALService, run_load,
                             run_serial)

    registry = ArtifactRegistry(max_batch=args.max_batch)
    tenants = [f"tenant{i}" for i in range(args.tenants)]
    t0 = time.perf_counter()
    for ti, tenant in enumerate(tenants):
        if args.load:
            registry.register(tenant, args.load)
            continue
        rng = np.random.default_rng(ti)
        key = jax.random.PRNGKey(ti)
        ds = make_regression(rng, n=256, d=4 * args.orgs)
        train, _ = train_test_split(ds, rng)
        xs = split_features(train.x, args.orgs)
        res = gal.fit(key, make_orgs(xs, Linear()), train.y,
                      get_loss("mse"),
                      GALConfig(rounds=args.rounds, engine="scan"))
        registry.register(tenant, res)
    src = f"loaded {args.load}" if args.load else "fit fresh"
    print(f"gal-service: {len(tenants)} tenants registered ({src}) in "
          f"{time.perf_counter() - t0:.2f} s")

    # synthesize single-row requests from each tenant's fitted geometry;
    # waves of `clients` consecutive requests share a tenant so the
    # batcher sees full per-tenant complements
    tenant_rows = {}
    for ti, tenant in enumerate(tenants):
        widths = registry.get(tenant).widths
        if any(w is None for w in widths):
            raise SystemExit("--service serves tabular artifacts only")
        rng = np.random.default_rng(100 + ti)
        tenant_rows[tenant] = [
            rng.normal(size=(64, w)).astype(np.float32) for w in widths]
    requests = []
    for i in range(args.requests):
        tenant = tenants[(i // max(args.clients, 1)) % len(tenants)]
        row = i % 64
        requests.append(
            (tenant, [x[row:row + 1] for x in tenant_rows[tenant]]))

    svc = GALService(registry, deadline_s=args.deadline_ms / 1e3,
                     flush_rows=args.flush_rows)
    t0 = time.perf_counter()
    buckets = sum(svc.warmup(t) for t in tenants)
    print(f"gal-service: warmed {buckets} bucket compilations "
          f"(max_batch={args.max_batch}) in "
          f"{time.perf_counter() - t0:.2f} s — no live request pays a "
          f"compile")
    try:
        serial = run_serial(registry, requests[:max(args.clients,
                                                    args.requests // 4)])
        load = run_load(svc, requests, clients=args.clients,
                        depth=args.depth)
    finally:
        svc.close()
    print(f"gal-service serial (1 client, blocked): "
          f"{serial['requests_per_sec']:.0f} req/s "
          f"p50={serial['p50_ms']:.2f} ms")
    print(f"gal-service batched ({args.clients} clients x depth "
          f"{args.depth}): {load['requests_per_sec']:.0f} req/s "
          f"p50={load['p50_ms']:.2f} ms p99={load['p99_ms']:.2f} ms "
          f"speedup={load['requests_per_sec'] / serial['requests_per_sec']:.1f}x")
    for tenant, st in sorted(svc.stats()["tenants"].items()):
        print(f"  {tenant}: {st['requests']} requests in {st['batches']} "
              f"launches ({st['rows_per_batch']:.1f} rows/launch)")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--gal-ensemble", action="store_true",
                    help="serve the stacked-round GAL Prediction Stage")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--orgs", type=int, default=4)
    ap.add_argument("--engine", default="scan",
                    choices=("auto", "scan", "shard", "grouped"),
                    help="--gal-ensemble fit engine; 'shard' places one org "
                         "per device (needs orgs | device count); 'grouped' "
                         "is the planner-driven fused engine for mixed "
                         "model sets")
    ap.add_argument("--hetero", action="store_true",
                    help="--gal-ensemble with a mixed GB/SVM-style model "
                         "set (model autonomy) fused by the org execution "
                         "planner; prints the per-group composition")
    ap.add_argument("--dms", action="store_true",
                    help="--gal-ensemble with Deep Model Sharing orgs "
                         "(one shared extractor + stacked per-round heads) "
                         "on the grouped engine; prints the model-memory "
                         "ledger's Tx saving")
    ap.add_argument("--save", default=None, metavar="DIR",
                    help="--gal-ensemble: persist the fitted ensemble as a "
                         "versioned artifact directory after the fit "
                         "(repro.checkpoint.save_artifact)")
    ap.add_argument("--load", default=None, metavar="DIR",
                    help="--gal-ensemble: SKIP the fit and serve a saved "
                         "artifact (fit once, serve forever); the jitted "
                         "predict path is compiled once and cached across "
                         "requests")
    ap.add_argument("--contributions", default=None,
                    choices=("loo", "shapley"),
                    help="--gal-ensemble: after the cold fit, score each "
                         "org's contributivity (leave-one-out or truncated "
                         "Shapley) via counterfactual refits resumed from "
                         "the mid-fit carry, and print the per-org table")
    ap.add_argument("--service", action="store_true",
                    help="run the multi-tenant inference service "
                         "(registry + bucketed batching, repro.serve) "
                         "under a concurrent load harness; combine with "
                         "--load DIR to serve a saved artifact per tenant")
    ap.add_argument("--tenants", type=int, default=2,
                    help="--service: registered collaborations")
    ap.add_argument("--clients", type=int, default=8,
                    help="--service: concurrent load-generator threads")
    ap.add_argument("--requests", type=int, default=256,
                    help="--service: total requests across all clients")
    ap.add_argument("--depth", type=int, default=4,
                    help="--service: requests each client keeps in flight")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="--service: largest bucket shape (jit cache holds "
                         "one compile per power-of-two bucket up to this)")
    ap.add_argument("--deadline-ms", type=float, default=10.0,
                    help="--service: max time a pending request waits "
                         "before its batch is flushed anyway")
    ap.add_argument("--flush-rows", type=int, default=16,
                    help="--service: rows that trigger an immediate flush")
    args = ap.parse_args()

    if args.load:
        conflicts = [flag for flag, on in (("--save", args.save),
                                           ("--hetero", args.hetero),
                                           ("--dms", args.dms),
                                           ("--contributions",
                                            args.contributions)) if on]
        if conflicts:
            ap.error(f"--load serves an already-fitted artifact; "
                     f"{'/'.join(conflicts)} choose fit-time behavior — "
                     f"drop them (or drop --load to fit)")

    if args.service:
        for flag, on in (("--save", args.save), ("--hetero", args.hetero),
                         ("--dms", args.dms),
                         ("--contributions", args.contributions)):
            if on:
                ap.error(f"--service serves fitted artifacts; {flag} "
                         f"chooses fit-time behavior — drop it")
        service_serve(args)
        return
    if args.gal_ensemble:
        gal_ensemble_serve(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --gal-ensemble is given")

    from repro.configs import get_arch
    from repro.configs.base import InputShape
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_device_mesh
    from repro.models import pspec as act_hints
    from repro.models import transformer as tfm
    from repro.train.steps import make_serve_step

    cfg = get_arch(args.arch, smoke=args.smoke)
    shape = tuple(int(x) for x in args.mesh.split(","))
    mesh = make_device_mesh(shape, ("data", "model"))
    act_hints.set_mesh(mesh)

    key = jax.random.PRNGKey(0)
    params = tfm.init_params(key, cfg)
    params = jax.device_put(params, shd.params_shardings(cfg, mesh, params))
    enc = None
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(
            key, (args.batch, cfg.num_frames, cfg.d_model), jnp.float32)
        enc = tfm.encode(params, cfg, frames)
    cache = tfm.init_cache(cfg, args.batch, args.cache_len, encoder_out=enc)
    ishape = InputShape("serve", args.cache_len, args.batch, "decode")
    c_sh = shd.cache_shardings(cfg, mesh, jax.eval_shape(lambda: cache),
                               ishape)
    cache = jax.device_put(cache, c_sh)

    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    tok = jax.random.randint(key, (args.batch, 1), 0, cfg.vocab)
    with mesh:
        # the compile call binds `logits`, so --steps 0 (compile-only)
        # still has a result to check for finiteness
        logits, cache = serve(params, cache, tok)  # compile
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            logits, cache = serve(params, cache, tok)
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        jax.block_until_ready(logits)
    dt = ((time.perf_counter() - t0) / args.steps if args.steps > 0
          else None)
    print(f"arch={cfg.arch} mesh={dict(mesh.shape)} batch={args.batch} "
          f"cache={args.cache_len}: {_fmt_ms(dt)}/token "
          f"finite={bool(jnp.all(jnp.isfinite(logits)))}")


if __name__ == "__main__":
    main()
