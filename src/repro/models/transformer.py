"""Unified architecture assembly for all 10 assigned configs.

One `Transformer` namespace of pure functions covering:
  dense GQA LMs          (llama3 / granite / stablelm / qwen3)
  capacity-routed MoE    (dbrx / phi3.5-moe)
  VLM token+patch decode (pixtral — vision frontend stubbed to embeddings)
  hybrid Mamba2 + shared attention (zamba2)
  attention-free RWKV6   (rwkv6-7b)
  encoder-decoder audio  (whisper — conv/mel frontend stubbed to embeddings)

Homogeneous layer stacks are stored stacked (L, ...) and executed with
jax.lax.scan (small HLO for the 512-device dry-run); zamba2 scans its
repeating unit. ``remat`` wraps scan bodies in jax.checkpoint.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import pspec
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    apply_mlp, apply_norm, dtype_of, embed_tokens, init_embedding, init_mlp,
    init_norm, unembed,
)


# =============================================================== param init
def _init_attn_block(rng, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    p = {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": attn.init_attention(k1, cfg),
        "ln2": init_norm(cfg, cfg.d_model),
    }
    if cfg.is_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg)
    else:
        p["mlp"] = init_mlp(k3, cfg)
    return p


def _init_mamba_block(rng, cfg: ModelConfig):
    k1, _ = jax.random.split(rng)
    return {"ln": init_norm(cfg, cfg.d_model),
            "mamba": ssm_lib.init_mamba(k1, cfg)}


def _init_rwkv_block(rng, cfg: ModelConfig):
    k1, k2 = jax.random.split(rng)
    return {"ln1": init_norm(cfg, cfg.d_model),
            "tmix": rwkv_lib.init_rwkv_tmix(k1, cfg),
            "ln2": init_norm(cfg, cfg.d_model),
            "cmix": rwkv_lib.init_rwkv_cmix(k2, cfg)}


def _stack(init_fn, rng, n: int):
    keys = jax.random.split(rng, n)
    return jax.vmap(init_fn)(keys)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(rng, 8)
    params: Dict[str, Any] = {"embed": init_embedding(ks[0], cfg),
                              "ln_f": init_norm(cfg, cfg.d_model)}
    unit = cfg.block_unit
    if unit == ("attn",):
        params["layers"] = _stack(lambda k: _init_attn_block(k, cfg),
                                  ks[1], cfg.n_layers)
    elif unit == ("rwkv",):
        params["layers"] = _stack(lambda k: _init_rwkv_block(k, cfg),
                                  ks[1], cfg.n_layers)
    elif "mamba" in unit:  # zamba2-style hybrid
        per_unit = sum(1 for b in unit if b == "mamba")
        n_units = cfg.n_layers // per_unit
        params["mamba_units"] = _stack(
            lambda k: _stack(lambda k2: _init_mamba_block(k2, cfg), k, per_unit),
            ks[1], n_units,
        )
        if cfg.shared_attn:
            params["shared_attn"] = _init_attn_block(ks[2], cfg)
    else:
        raise ValueError(f"unsupported block unit {unit}")

    if cfg.is_encoder_decoder:
        def enc_block(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": init_norm(cfg, cfg.d_model),
                    "attn": attn.init_attention(k1, cfg),
                    "ln2": init_norm(cfg, cfg.d_model),
                    "mlp": init_mlp(k2, cfg)}

        params["encoder"] = _stack(enc_block, ks[3], cfg.encoder_layers)
        params["enc_ln_f"] = init_norm(cfg, cfg.d_model)

        def cross_block(k):
            return {"ln": init_norm(cfg, cfg.d_model),
                    "attn": attn.init_attention(k, cfg, cross=True)}

        params["cross"] = _stack(cross_block, ks[4], cfg.n_layers)
    if cfg.frontend == "vision":
        # projector from (stub) vision embeddings to d_model
        params["proj"] = (jax.random.normal(ks[5], (cfg.d_model, cfg.d_model),
                                            jnp.float32)
                          * cfg.d_model ** -0.5).astype(dtype_of(cfg))
    return params


# ============================================================ forward (train)
def _attn_block_fwd(block, cfg: ModelConfig, x, positions, *, causal=True,
                    window=None, flash=False):
    h = attn.attention_train(block["attn"], cfg, apply_norm(block["ln1"], x),
                             positions, causal=causal, window=window,
                             flash=flash)
    x = x + h
    hin = apply_norm(block["ln2"], x)
    if cfg.is_moe:
        h, aux = moe_lib.apply_moe(block["moe"], cfg, hin)
    else:
        h, aux = apply_mlp(block["mlp"], hin, cfg.act), jnp.zeros((), jnp.float32)
    return x + h, aux


def _rwkv_block_fwd(block, cfg: ModelConfig, x):
    x = x + rwkv_lib.rwkv_tmix_train(block["tmix"], cfg,
                                     apply_norm(block["ln1"], x))
    x = x + rwkv_lib.rwkv_cmix(block["cmix"], apply_norm(block["ln2"], x))
    return x, jnp.zeros((), jnp.float32)


def _mamba_block_fwd(block, cfg: ModelConfig, x):
    return x + ssm_lib.mamba_train(block["mamba"], cfg,
                                   apply_norm(block["ln"], x))


def _group_of(n: int) -> int:
    """Divisor of n nearest sqrt(n) (2-level remat group size)."""
    import math
    best, target = 1, math.sqrt(n)
    for d in range(1, n + 1):
        if n % d == 0 and abs(d - target) < abs(best - target):
            best = d
    return best


def _scan_layers(layers, body, x, aux0, remat: bool, group: bool = False):
    """Layer-stack execution. With remat: TWO-LEVEL (sqrt-L) checkpointing —
    an outer scan over G groups stashes only group-boundary activations; each
    group's inner scan re-stashes its layers transiently during backward.
    Cuts the dominant (L, B, S, d) stash to ~(G + L/G) layers' worth at the
    cost of one extra forward recompute (+~25% FLOPs), the standard
    memory-optimal remat schedule."""
    n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    fn = jax.checkpoint(body) if remat else body

    def scan_body(carry, layer):
        x, aux = carry
        # barrier pins the stash dtype: without it XLA hoists the backward's
        # first f32 convert of x into the per-layer stash, doubling it
        x = jax.lax.optimization_barrier(x)
        x, a = fn(layer, x)
        return (x, aux + a), None

    g = _group_of(n_layers) if (remat and group) else 1
    if remat and group and 1 < g < n_layers:
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(g, n_layers // g, *a.shape[1:]), layers)

        @jax.checkpoint
        def group_fn(carry, group_layers):
            return jax.lax.scan(scan_body, carry, group_layers)

        (x, aux), _ = jax.lax.scan(group_fn, (x, aux0), grouped)
        return x, aux
    (x, aux), _ = jax.lax.scan(scan_body, (x, aux0), layers)
    return x, aux


def _decoder_stack(params, cfg: ModelConfig, x, positions, *, flash=False,
                   encoder_out=None):
    """Run the configured layer stack on embeddings x (B, S, d)."""
    # The residual-stream layout is anchored by REPLICATING the token table
    # (see sharding.param_pspec): the gather then yields batch-sharded,
    # d-replicated x directly. Constraining x here instead would force a
    # d-reshard inside the microbatch scan, which both costs ~290 GiB of
    # activation all-gathers per step AND trips an XLA SPMD verifier bug.
    aux = jnp.zeros((), jnp.float32)
    unit = cfg.block_unit
    if unit == ("attn",):
        if cfg.is_encoder_decoder:
            # scan over zipped (self-attn layer, cross-attn layer) stacks
            def encdec_body(layer_cross, xx):
                layer, cross = layer_cross
                h = attn.attention_train(
                    layer["attn"], cfg, apply_norm(layer["ln1"], xx),
                    positions, causal=True, window=cfg.window, flash=flash)
                xx = xx + h
                xx = xx + attn.attention_train(
                    cross["attn"], cfg, apply_norm(cross["ln"], xx), positions,
                    kv_src=encoder_out)
                xx = xx + apply_mlp(layer["mlp"], apply_norm(layer["ln2"], xx),
                                    cfg.act)
                return xx, jnp.zeros((), jnp.float32)

            return _scan_layers((params["layers"], params["cross"]),
                                encdec_body, x, aux, cfg.remat,
                                cfg.remat_group)
        body = lambda layer, xx: _attn_block_fwd(
            layer, cfg, xx, positions, causal=True, window=cfg.window,
            flash=flash)
        return _scan_layers(params["layers"], body, x, aux, cfg.remat,
                            cfg.remat_group)
    if unit == ("rwkv",):
        body = lambda layer, xx: _rwkv_block_fwd(layer, cfg, xx)
        return _scan_layers(params["layers"], body, x, aux, cfg.remat,
                            cfg.remat_group)
    # hybrid: scan units of [mamba x per_unit (+ shared attn)]; each block
    # is checkpointed so the quadratic intra-chunk SSD temporaries are
    # rematerialized instead of stashed (measured 131 GiB/device without)
    shared = params.get("shared_attn")
    mamba_fwd = (jax.checkpoint(lambda l, xx: _mamba_block_fwd(l, cfg, xx))
                 if cfg.remat else (lambda l, xx: _mamba_block_fwd(l, cfg, xx)))
    attn_fwd = lambda blk, xx: _attn_block_fwd(
        blk, cfg, xx, positions, causal=True, window=cfg.window, flash=flash)
    if cfg.remat:
        attn_fwd = jax.checkpoint(attn_fwd)

    def unit_body(carry, unit_params):
        x, aux = carry

        def mamba_body(xx, layer):
            return mamba_fwd(layer, xx), None

        x, _ = jax.lax.scan(mamba_body, x, unit_params)
        if shared is not None:
            x, a = attn_fwd(shared, x)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(unit_body, (x, aux), params["mamba_units"])
    return x, aux


def encode(params, cfg: ModelConfig, frames):
    """Whisper encoder over stub frame embeddings (B, F, d) -> (B, F, d)."""
    positions = jnp.broadcast_to(
        jnp.arange(frames.shape[1], dtype=jnp.int32), frames.shape[:2])
    x = frames.astype(dtype_of(cfg))

    def body(layer, xx):
        h = attn.attention_train(layer["attn"], cfg,
                                 apply_norm(layer["ln1"], xx), positions,
                                 causal=False)
        xx = xx + h
        return xx + apply_mlp(layer["mlp"], apply_norm(layer["ln2"], xx),
                              cfg.act), jnp.zeros((), jnp.float32)

    x, _ = _scan_layers(params["encoder"], body, x,
                        jnp.zeros((), jnp.float32), cfg.remat)
    return apply_norm(params["enc_ln_f"], x)


def apply(params, cfg: ModelConfig, tokens, *, patches=None, frames=None,
          flash: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.

    tokens: (B, S_text) int32. patches: VLM stub embeddings (B, P, d).
    frames: audio stub embeddings (B, F, d) for the enc-dec arch.
    Returns (logits (B, S_total, vocab) f32, aux_loss).
    """
    x = embed_tokens(params["embed"], tokens).astype(dtype_of(cfg))
    if cfg.frontend == "vision" and patches is not None:
        pe = patches.astype(dtype_of(cfg)) @ params["proj"]
        x = jnp.concatenate([pe, x], axis=1)       # image tokens first
    # NOTE: constraining x right after the token gather trips an XLA SPMD
    # verifier bug (dynamic-slice size mismatch) when the gather sits inside
    # the grad-accumulation scan; propagation handles it fine unconstrained.
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
    encoder_out = None
    if cfg.is_encoder_decoder:
        if frames is None:
            raise ValueError("enc-dec arch requires frames")
        encoder_out = encode(params, cfg, frames)
    x, aux = _decoder_stack(params, cfg, x, positions, flash=flash,
                            encoder_out=encoder_out)
    x = apply_norm(params["ln_f"], x)
    # logits stay in the compute dtype: f32 logits would push f32 cotangents
    # through the whole backward pass and double the remat stash (measured:
    # 12 GiB/device on stablelm train_4k; see EXPERIMENTS.md SS Perf). Losses
    # upcast internally.
    logits = unembed(params["embed"], x)
    logits = pspec.constrain(
        logits, P(pspec.batch_axis(x.shape[0]), None,
                  pspec.model_axis(cfg.vocab)))
    return logits, aux


# ================================================================= decode
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               encoder_out: Optional[jnp.ndarray] = None) -> Dict[str, Any]:
    dt = dtype_of(cfg)
    unit = cfg.block_unit
    cache: Dict[str, Any] = {}
    if unit == ("attn",):
        def one(_):
            return attn.init_kv_cache(cfg, batch, max_len, dt)

        cache["attn"] = jax.vmap(one)(jnp.arange(cfg.n_layers))
    elif unit == ("rwkv",):
        def one(_):
            return rwkv_lib.init_rwkv_cache(cfg, batch, dt)

        cache["rwkv"] = jax.vmap(one)(jnp.arange(cfg.n_layers))
    else:
        per_unit = sum(1 for b in unit if b == "mamba")
        n_units = cfg.n_layers // per_unit

        def one_unit(_):
            def one(_):
                return ssm_lib.init_mamba_cache(cfg, batch, dt)

            return jax.vmap(one)(jnp.arange(per_unit))

        cache["mamba"] = jax.vmap(one_unit)(jnp.arange(n_units))
        if cfg.shared_attn:
            def one(_):
                return attn.init_kv_cache(cfg, batch, max_len, dt)

            cache["shared_attn"] = jax.vmap(one)(jnp.arange(n_units))
    if cfg.is_encoder_decoder:
        if encoder_out is None:
            raise ValueError("enc-dec cache needs encoder_out")
        cache["encoder_out"] = encoder_out
    return cache


def _attn_block_decode(block, cfg, x, layer_cache, cross=None, cross_params=None):
    h, new_cache = attn.attention_decode(
        block["attn"], cfg, apply_norm(block["ln1"], x), layer_cache)
    x = x + h
    if cross is not None:
        h, _ = attn.attention_decode(cross_params["attn"], cfg,
                                     apply_norm(cross_params["ln"], x),
                                     None, kv_src=cross)
        x = x + h
    hin = apply_norm(block["ln2"], x)
    if cfg.is_moe:
        h, _ = moe_lib.apply_moe(block["moe"], cfg, hin)
    else:
        h = apply_mlp(block["mlp"], hin, cfg.act)
    return x + h, new_cache


def decode_step(params, cfg: ModelConfig, token, cache
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One-token decode. token: (B, 1) int32. Returns (logits (B,1,V), cache)."""
    x = embed_tokens(params["embed"], token).astype(dtype_of(cfg))
    unit = cfg.block_unit
    new_cache = dict(cache)
    if unit == ("attn",):
        if cfg.is_encoder_decoder:
            enc = cache["encoder_out"]
            caches = cache["attn"]
            outs = []
            for i in range(cfg.n_layers):
                layer = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
                cross = jax.tree_util.tree_map(lambda a: a[i], params["cross"])
                lc = jax.tree_util.tree_map(lambda a: a[i], caches)
                x, nc = _attn_block_decode(layer, cfg, x, lc, cross=enc,
                                           cross_params=cross)
                outs.append(nc)
            new_cache["attn"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            def body(x, inputs):
                layer, lc = inputs
                x, nc = _attn_block_decode(layer, cfg, x, lc)
                return x, nc

            x, stacked = jax.lax.scan(body, x, (params["layers"], cache["attn"]))
            new_cache["attn"] = stacked
    elif unit == ("rwkv",):
        def body(x, inputs):
            layer, lc = inputs
            h, frag = rwkv_lib.rwkv_tmix_decode(
                layer["tmix"], cfg, apply_norm(layer["ln1"], x), lc)
            x = x + h
            xn = apply_norm(layer["ln2"], x)
            x = x + rwkv_lib.rwkv_cmix(layer["cmix"], xn, lc["cmix_prev"])
            nc = {"state": frag["state"], "tmix_prev": frag["tmix_prev"],
                  "cmix_prev": xn}
            return x, nc

        x, stacked = jax.lax.scan(body, x, (params["layers"], cache["rwkv"]))
        new_cache["rwkv"] = stacked
    else:  # hybrid
        shared = params.get("shared_attn")

        def unit_body(carry, inputs):
            x = carry
            unit_params, unit_cache, sa_cache = inputs

            def mbody(x, z):
                layer, lc = z
                h, nc = ssm_lib.mamba_decode(layer["mamba"], cfg,
                                             apply_norm(layer["ln"], x), lc)
                return x + h, nc

            x, new_mc = jax.lax.scan(mbody, x, (unit_params, unit_cache))
            new_sa = sa_cache
            if shared is not None:
                x, new_sa = _attn_block_decode(shared, cfg, x, sa_cache)
            return x, (new_mc, new_sa)

        sa_caches = cache.get("shared_attn")
        x, (new_mc, new_sa) = jax.lax.scan(
            unit_body, x, (params["mamba_units"], cache["mamba"], sa_caches))
        new_cache["mamba"] = new_mc
        if sa_caches is not None:
            new_cache["shared_attn"] = new_sa
    x = apply_norm(params["ln_f"], x)
    logits = unembed(params["embed"], x).astype(jnp.float32)  # decode: tiny
    return logits, new_cache
