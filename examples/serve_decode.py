"""Prediction-stage serving: batched single-token decode against a KV/state
cache for any assigned architecture — the step the decode_32k / long_500k
dry-run shapes lower.

GAL context: in the paper's Prediction Stage each org serves its local
per-round models and Alice assembles F^T = F^0 + sum_t eta_t sum_m w_mt f_mt.
Here one org serves its model and reports logits; the (eta, w) assembly is a
dot product on Alice's side (shown at the end).

Run: PYTHONPATH=src python examples/serve_decode.py --arch zamba2-2.7b
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_arch
from repro.models import transformer as tfm
from repro.train.steps import make_serve_step
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-2.7b", choices=ALL_ARCHS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=True)
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(key, cfg)
    serve_step = jax.jit(make_serve_step(cfg))

    enc = None
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(
            key, (args.batch, cfg.num_frames, cfg.d_model), jnp.float32)
        enc = tfm.encode(params, cfg, frames)
    cache = tfm.init_cache(cfg, args.batch, args.cache_len, encoder_out=enc)

    tok = jax.random.randint(key, (args.batch, 1), 0, cfg.vocab)
    # warmup + timed decode loop
    logits, cache = serve_step(params, cache, tok)
    t0 = time.perf_counter()
    etas, weights = [], []
    f_alice = jnp.zeros((args.batch, cfg.vocab))
    for step in range(args.steps):
        logits, cache = serve_step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        # Alice-side assembly with this round's (eta, w) — one org shown
        f_alice = f_alice + 1.0 * 1.0 * logits[:, 0]
    dt = (time.perf_counter() - t0) / args.steps
    print(f"arch={args.arch} batch={args.batch} cache={args.cache_len} "
          f"steps={args.steps}")
    print(f"decode latency (CPU smoke config): {dt * 1e3:.2f} ms/token")
    print(f"assembled prediction shape: {f_alice.shape}, "
          f"finite: {bool(jnp.all(jnp.isfinite(f_alice)))}")


if __name__ == "__main__":
    main()
