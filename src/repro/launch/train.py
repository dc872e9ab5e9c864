"""Production training launcher: one GAL organization's local fit on the
production mesh.

On a real TPU slice this runs under the standard multi-host bootstrap
(jax.distributed.initialize from TPU env vars); on this CPU container use
REPRO_FORCE_DEVICES=8 with --mesh 2,4 for a faithful small-scale run.

Examples:
  # real run, smoke-scale, 8 fake devices
  REPRO_FORCE_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
      --arch llama3-8b --smoke --mesh 2,4 --steps 4 --batch 8 --seq 64
"""
from repro.utils.force_devices import apply_force_devices
apply_force_devices()

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1,1",
                    help="data,model axis sizes (e.g. 16,16)")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--loss-kind", default="lm_xent",
                    choices=("lm_xent", "gal_residual"))
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()

    from repro.configs import get_arch
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_device_mesh
    from repro.models import pspec as act_hints
    from repro.models import transformer as tfm
    from repro.train.steps import make_train_step
    from repro.data.tokens import make_token_stream, token_batches

    cfg = get_arch(args.arch, smoke=args.smoke)
    shape = tuple(int(x) for x in args.mesh.split(","))
    mesh = make_device_mesh(shape, ("data", "model"))
    act_hints.set_mesh(mesh)
    print(f"mesh={dict(mesh.shape)} devices={mesh.size} arch={cfg.arch}")

    key = jax.random.PRNGKey(0)
    params = tfm.init_params(key, cfg)
    p_sh = shd.params_shardings(cfg, mesh, params)
    params = jax.device_put(params, p_sh)
    step_fn, opt = make_train_step(cfg, args.loss_kind, lr=args.lr,
                                   microbatch=args.microbatch)
    opt_state = opt.init(params)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    rng_np = np.random.default_rng(0)
    stream = make_token_stream(rng_np, cfg.vocab, 100_000)
    batches = token_batches(stream, args.batch, args.seq, rng_np)
    with mesh:
        for step in range(args.steps):
            toks, labels = next(batches)
            batch = {"tokens": jnp.asarray(toks)}
            if args.loss_kind == "lm_xent":
                batch["labels"] = jnp.asarray(labels)
            else:
                from repro.core.gal_lm import compute_residual
                f0 = jnp.zeros((args.batch, args.seq, cfg.vocab))
                batch["residual"] = compute_residual(
                    jnp.asarray(labels), f0, use_kernel=False)
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            print(f"step {step}: loss={loss:.4f} ({time.time() - t0:.1f}s)",
                  flush=True)
    if args.checkpoint_dir:
        from repro.checkpoint import save_pytree
        save_pytree(f"{args.checkpoint_dir}/{cfg.arch}_final.npz", params)
        print(f"saved params to {args.checkpoint_dir}")


if __name__ == "__main__":
    main()
