"""End-to-end driver: GAL over LM organizations on a token task — the
paper's protocol applied to the assigned-architecture substrate.

Two orgs hold vertically-split token views (vocab factorization: org 0 sees
the high bits, org 1 the low bits); Alice holds next-token labels. Per
assistance round each org runs `--local-steps` AdamW steps of its sequence
model on the broadcast pseudo-residual, then Alice fits assistance weights
and line-searches eta.

`--mixed` gives org 1 an RWKV architecture instead of a transformer — the
model-autonomy demo: the planner keys the orgs into two groups and the
grouped engine runs both inside one scanned round loop. `--artifact-dir`
additionally demonstrates the fit lifecycle: fit half the rounds, save the
gal-artifact/v1 directory, load it back, and resume to the full count —
then verifies the resumed history continues the saved one.

Defaults are CPU-sized (a few minutes). `--preset 100m` trains ~100M-param
orgs for a few hundred local steps — the production-scale configuration for
a real accelerator host.

Run: PYTHONPATH=src python examples/train_lm_gal.py [--preset 100m] [--mixed]
"""
import argparse
import math
from dataclasses import replace

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import artifact_info, load_lm_artifact, save_lm_artifact
from repro.configs import get_arch
from repro.core import gal_lm
from repro.data.tokens import make_token_stream, token_batches
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=("smoke", "100m"), default="smoke")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--mixed", action="store_true",
                    help="org 1 runs RWKV instead of a transformer "
                         "(two plan groups, grouped engine)")
    ap.add_argument("--artifact-dir", default=None,
                    help="demo the fit lifecycle: fit half the rounds, "
                         "save here, load, resume to the full count")
    args = ap.parse_args()

    base = get_arch("llama3-8b", smoke=True)
    if args.preset == "100m":
        cfg = replace(base, n_layers=12, d_model=768, n_heads=12,
                      n_kv_heads=4, d_ff=2048, vocab=8192)
        local_steps = args.local_steps or 200
        batch, seq = args.batch or 16, args.seq or 256
    else:
        cfg = replace(base, vocab=1024)
        local_steps = args.local_steps or 10
        batch, seq = args.batch or 4, args.seq or 64

    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    stream = make_token_stream(rng_np, cfg.vocab, 200_000)
    toks, labels = next(token_batches(stream, batch, seq, rng_np))
    toks, labels = jnp.asarray(toks), jnp.asarray(labels)

    root = int(math.isqrt(cfg.vocab))
    cfg1 = cfg
    if args.mixed:
        rwkv = get_arch("rwkv6-7b", smoke=True)
        cfg1 = replace(rwkv, d_model=cfg.d_model, n_layers=cfg.n_layers,
                       d_ff=cfg.d_ff, vocab=cfg.vocab)

    def make_orgs():
        orgs = [
            gal_lm.LMOrganization(0, cfg, lambda t: (t // root) % cfg.vocab),
            gal_lm.LMOrganization(1, cfg1, lambda t: (t % root) % cfg.vocab),
        ]
        for i, org in enumerate(orgs):
            org.init(jax.random.fold_in(key, i), lr=3e-3)
        return orgs

    orgs = make_orgs()
    for org in orgs:
        n = sum(x.size for x in jax.tree_util.tree_leaves(org.params))
        print(f"org {org.index}: arch={org.cfg.arch} params={n:,}")
    print(f"batch={batch} seq={seq} rounds={args.rounds} "
          f"local_steps={local_steps} mixed={args.mixed}")

    res = gal_lm.fit_lm(key, orgs, toks, labels, rounds=args.rounds,
                        local_steps=local_steps)
    print(f"engine={res.engine} plan: {res.plan.describe()}")
    for t, xent in enumerate(res.history["train_xent"]):
        eta = f" eta={res.etas[t-1]:.2f}" if t else ""
        print(f" round {t}: train xent={xent:.4f}{eta}")
    drop = res.history["train_xent"][0] - res.history["train_xent"][-1]
    print(f"xent improvement over {args.rounds} assistance rounds: {drop:.4f}")
    assert drop > 0, "GAL rounds must decrease the overarching loss"

    if args.artifact_dir:
        half = max(1, args.rounds // 2)
        res_half = gal_lm.fit_lm(key, make_orgs(), toks, labels,
                                 rounds=half, local_steps=local_steps)
        path = save_lm_artifact(res_half, args.artifact_dir)
        info = artifact_info(path)
        print(f"saved {path}: kind={info['kind']} t_next={info['t_next']} "
              f"groups={info['n_groups']}")
        loaded = load_lm_artifact(path)
        views = jnp.stack([org.view_fn(toks) for org in res_half.orgs])
        f_loaded = loaded.predict(views=views)
        print(f"loaded artifact predicts {f_loaded.shape} with no orgs "
              f"attached")
        res_resumed = gal_lm.fit_lm(key, make_orgs(), toks, labels,
                                    rounds=args.rounds,
                                    local_steps=local_steps,
                                    resume_from=path)
        same = np.allclose(res_resumed.history["train_xent"],
                           res.history["train_xent"])
        print(f"resumed {half}->{args.rounds} rounds; history matches "
              f"uninterrupted fit: {same}")
        assert same, "resume must continue the collaboration exactly"


if __name__ == "__main__":
    main()
