"""Step functions lowered by the launcher/dry-run and used by the examples.

Three training objectives:

  gal_residual_loss       — PAPER-FAITHFUL GAL local fit: the org's model
      regresses (ell_2) onto the dense broadcast pseudo-residual
      r in R^{B x S x V} (paper Alg. 1 step 3; Table 9 default ell_2).
  gal_residual_topk_loss  — BEYOND-PAPER transport: Alice broadcasts the
      residual compressed to top-K (values, indices) per token; the implicit
      off-support entries of r are 0, so the exact ell_2 objective is
          ||f||^2 - ||f_sel||^2 + ||f_sel - vals||^2
      computed without materializing the dense (B, S, V) target. Recorded
      separately in EXPERIMENTS.md SS Perf.
  lm_xent_loss            — Alice's own overarching L1 (next-token xent),
      used by the end-to-end example and the 'Alone/Joint' LM baselines.

serve_step is the paper's Prediction Stage at one org: a single new token
against a seq_len KV/state cache.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.optim.optimizers import adamw, apply_updates

AUX_COEF = 0.01  # MoE load-balance weight


def _forward(params, cfg: ModelConfig, batch, flash: bool):
    kwargs = {}
    if cfg.frontend == "vision":
        kwargs["patches"] = batch["patches"]
    if cfg.is_encoder_decoder:
        kwargs["frames"] = batch["frames"]
    logits, aux = tfm.apply(params, cfg, batch["tokens"], flash=flash, **kwargs)
    if cfg.frontend == "vision":
        logits = logits[:, cfg.num_patches:, :]   # loss on text positions
    return logits, aux


def gal_residual_loss(params, cfg: ModelConfig, batch, flash: bool = False):
    """ell_2 regression onto the dense broadcast pseudo-residual."""
    logits, aux = _forward(params, cfg, batch, flash)
    r = batch["residual"].astype(logits.dtype)
    diff = logits - r
    l2 = jnp.mean(jnp.square(diff).astype(jnp.float32))
    return l2 + AUX_COEF * aux, {"fit_l2": l2, "aux": aux}


def gal_residual_topk_loss(params, cfg: ModelConfig, batch,
                           flash: bool = False):
    """ell_2 onto a top-K compressed residual (exact when the true residual
    is supported on the K indices; the GAL residual y - softmax(F) is
    concentrated, making the truncation error tiny)."""
    logits, aux = _forward(params, cfg, batch, flash)
    idx = batch["residual_idx"]                      # (B, S, K) int32
    vals = batch["residual_vals"]
    vals = vals.astype(logits.dtype)
    f_sel = jnp.take_along_axis(logits, idx, axis=-1)
    total = (jnp.sum(jnp.square(logits), axis=-1, dtype=jnp.float32)
             - jnp.sum(jnp.square(f_sel), axis=-1, dtype=jnp.float32)
             + jnp.sum(jnp.square(f_sel - vals), axis=-1, dtype=jnp.float32))
    l2 = jnp.mean(total) / logits.shape[-1]
    return l2 + AUX_COEF * aux, {"fit_l2": l2, "aux": aux}


def lm_xent_loss(params, cfg: ModelConfig, batch, flash: bool = False):
    logits, aux = _forward(params, cfg, batch, flash)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss + AUX_COEF * aux, {"xent": loss, "aux": aux}


LOSS_FNS: Dict[str, Callable] = {
    "gal_residual": gal_residual_loss,
    "gal_residual_topk": gal_residual_topk_loss,
    "lm_xent": lm_xent_loss,
}


def make_train_step(cfg: ModelConfig, loss_kind: str = "gal_residual",
                    lr: float = 3e-4, weight_decay: float = 0.1,
                    flash: bool = False, microbatch: int = 1):
    """Returns (train_step, optimizer). train_step: (params, opt_state, batch)
    -> (params, opt_state, metrics).

    microbatch > 1 scans gradient-accumulation slices of the global batch
    (activation memory / microbatch; grads accumulate in f32).

    Equal arguments return the same two objects (the factory is pure), so
    a GAL round program, which is keyed on its steps' identity, is found
    again by the next fit of freshly built organizations. Unhashable
    arguments (a learning rate given as an array) build a new pair."""
    args = (cfg, loss_kind, lr, weight_decay, flash, microbatch)
    try:
        hash(args)
    except TypeError:
        return _build_train_step(*args)
    return _cached_train_step(*args)


def _build_train_step(cfg, loss_kind, lr, weight_decay, flash, microbatch):
    loss_fn = LOSS_FNS[loss_kind]
    opt = adamw(lr, weight_decay=weight_decay)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, flash=flash), has_aux=True
        )(params)

    def accum_unrolled(params, batch):
        # STATIC slices: a lax.scan over microbatches dynamic-slices the
        # batch and trips an XLA SPMD verifier bug for the MoE archs
        mbs = batch[next(iter(batch))].shape[0] // microbatch
        g_acc = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        loss_sum = 0.0
        for i in range(microbatch):
            mb = jax.tree_util.tree_map(
                lambda x: jax.lax.slice_in_dim(x, i * mbs, (i + 1) * mbs,
                                               axis=0), batch)
            if i:
                # serialize: tie this slice to the previous accumulator so
                # the microbatch stashes never coexist in memory
                mb, g_acc = jax.lax.optimization_barrier((mb, g_acc))
            (loss, _), grads = grads_of(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            loss_sum = loss_sum + loss
        return g_acc, loss_sum

    def accum_scan(params, batch):
        # default path: one live stash, best memory (non-MoE archs)
        def split(x):
            return x.reshape(microbatch, x.shape[0] // microbatch,
                             *x.shape[1:])

        micro = jax.tree_util.tree_map(split, batch)

        def accum(carry, mb):
            g_acc, loss_acc = carry
            (loss, _), grads = grads_of(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            return (g_acc, loss_acc + loss), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g_acc, loss_sum), _ = jax.lax.scan(accum, (g0, 0.0), micro)
        return g_acc, loss_sum

    def train_step(params, opt_state, batch):
        if microbatch > 1:
            if cfg.is_moe:
                g_acc, loss_sum = accum_unrolled(params, batch)
            else:
                g_acc, loss_sum = accum_scan(params, batch)
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / microbatch).astype(p.dtype), g_acc, params)
            loss = loss_sum / microbatch
            metrics = {"loss": loss}
        else:
            (loss, metrics), grads = grads_of(params, batch)
            metrics = dict(metrics, loss=loss)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    return train_step, opt


_cached_train_step = lru_cache(maxsize=64)(_build_train_step)


def run_local_steps(train_step, params, opt_state, batch, steps: int):
    """Run ``steps`` optimizer steps over one fixed batch as a single
    lax.scan: a GAL organization's per-round local fit compiles to one device
    program instead of ``steps`` Python dispatches. ``train_step`` may be a
    raw step or a vmapped (org-stacked) one — the fused LM engine passes the
    latter. Returns (params, opt_state, stacked per-step metrics)."""

    def body(carry, _):
        p, s = carry
        p, s, metrics = train_step(p, s, batch)
        return (p, s), metrics

    (params, opt_state), metrics = jax.lax.scan(
        body, (params, opt_state), None, length=steps)
    return params, opt_state, metrics


def make_prefill_step(cfg: ModelConfig, flash: bool = False):
    """Inference prefill: full-sequence forward producing logits (scoring).
    Cache materialization is left to the serving layer (noted in DESIGN.md)."""

    def prefill_step(params, batch):
        logits, _ = _forward(params, cfg, batch, flash)
        return logits

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """Prediction-stage decode: ONE new token against a seq_len cache."""

    def serve_step(params, cache, token):
        logits, new_cache = tfm.decode_step(params, cfg, token, cache)
        return logits, new_cache

    return serve_step
