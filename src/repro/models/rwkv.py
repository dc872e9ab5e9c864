"""RWKV6 "Finch" block (arXiv:2404.05892): data-dependent decay linear
attention with constant-size state — the assigned attention-free arch.

Time-mix (per head, k/v dims = head size):
    y_t = r_t^T (S_t + diag(u) k_t v_t^T)
    S_{t+1} = diag(w_t) S_t + k_t v_t^T,   w_t = exp(-exp(w0 + lora_w(x)))
Data dependence: token-shift mixing coefficients and the decay w_t are
low-rank functions of the input (the Finch contribution).

Training runs a lax.scan over time carrying S (B, H, K, V); decode is a single
state update. Channel-mix is the RWKV squared-relu FFN.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import pspec
from repro.models.layers import dense_init, dtype_of

LORA_R = 32
CHUNK = 32    # factorized-WKV chunk (f32-safe with decay floor)
_MIX = ("r", "k", "v", "w", "g")


def rwkv_dims(cfg: ModelConfig):
    hd = 64 if cfg.d_model % 64 == 0 else cfg.d_model // cfg.n_heads
    heads = cfg.d_model // hd
    return heads, hd


def init_rwkv_tmix(rng, cfg: ModelConfig):
    d = cfg.d_model
    h, hd = rwkv_dims(cfg)
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 10)
    p = {
        "mu": (jax.random.uniform(ks[0], (len(_MIX), d), jnp.float32)).astype(dt),
        "mix_lora_a": dense_init(ks[1], d, LORA_R * len(_MIX), dt),
        "mix_lora_b": (jax.random.normal(ks[2], (len(_MIX), LORA_R, d),
                                         jnp.float32) * 0.01).astype(dt),
        "wr": dense_init(ks[3], d, d, dt),
        "wk": dense_init(ks[4], d, d, dt),
        "wv": dense_init(ks[5], d, d, dt),
        "wg": dense_init(ks[6], d, d, dt),
        "wo": dense_init(ks[7], d, d, dt),
        "w0": jnp.full((d,), -1.0, jnp.float32),       # base decay
        "w_lora_a": dense_init(ks[8], d, LORA_R, dt),
        "w_lora_b": (jax.random.normal(ks[9], (LORA_R, d), jnp.float32)
                     * 0.01).astype(dt),
        "u": jnp.zeros((d,), jnp.float32),             # current-token bonus
        "ln_scale": jnp.ones((d,), jnp.float32),       # per-head group norm
    }
    return p


def _token_shift(params, x, x_prev):
    """Finch data-dependent token shift. x, x_prev: (B, S, d).
    Returns dict name -> mixed input (B, S, d)."""
    delta = x_prev - x
    lora = jnp.tanh(x @ params["mix_lora_a"])            # (B,S,R*5)
    lora = lora.reshape(*x.shape[:-1], len(_MIX), LORA_R)
    dyn = jnp.einsum("bsmr,mrd->bsmd", lora, params["mix_lora_b"])
    mix = jax.nn.sigmoid(params["mu"][None, None] + dyn)  # (B,S,5,d)
    return {name: x + delta * mix[:, :, i] for i, name in enumerate(_MIX)}


LOG_DECAY_FLOOR = -2.0   # per-step log-decay clamp (f32 range safety in the
                         # factorized chunked WKV; see rwkv_tmix_train)


def _decay(params, xw):
    """w_t in (0,1): exp(clip(-exp(w0 + lora), FLOOR, 0)).
    xw: (B,S,d) -> (B,S,d) f32. The floor keeps exp(-cumsum) within f32 range
    for the chunked factorization (chunk 32 -> max exponent 64)."""
    lora = jnp.tanh(xw @ params["w_lora_a"]) @ params["w_lora_b"]
    ld = jnp.clip(-jnp.exp(params["w0"] + lora.astype(jnp.float32)),
                  LOG_DECAY_FLOOR, 0.0)
    return jnp.exp(ld)


def _group_norm(x, scale, heads, eps=1e-6):
    b, s, d = x.shape
    xg = x.reshape(b, s, heads, d // heads)
    mu = jnp.mean(xg, axis=-1, keepdims=True, dtype=jnp.float32)
    var = jnp.mean(jnp.square(xg), axis=-1, keepdims=True,
                   dtype=jnp.float32) - jnp.square(mu)
    inv = jax.lax.rsqrt(var + eps)
    out = (xg - mu.astype(x.dtype)) * inv.astype(x.dtype)
    return out.reshape(b, s, d) * scale.astype(x.dtype)


def rwkv_tmix_train(params, cfg: ModelConfig, x, x_prev_last=None):
    """x: (B, S, d) -> (B, S, d). x_prev_last: carry of last token (B,1,d)."""
    b, s, d = x.shape
    h, hd = rwkv_dims(cfg)
    if x_prev_last is None:
        x_prev_last = jnp.zeros((b, 1, d), x.dtype)
    x_prev = jnp.concatenate([x_prev_last, x[:, :-1]], axis=1)
    mixed = _token_shift(params, x, x_prev)
    r = (mixed["r"] @ params["wr"]).reshape(b, s, h, hd)
    k = (mixed["k"] @ params["wk"]).reshape(b, s, h, hd)
    v = (mixed["v"] @ params["wv"]).reshape(b, s, h, hd)
    g = jax.nn.silu(mixed["g"] @ params["wg"])
    w = _decay(params, mixed["w"]).reshape(b, s, h, hd)      # f32
    u = params["u"].reshape(h, hd)

    bax = pspec.batch_axis(b)
    hax = pspec.model_axis(h)
    spec = P(bax, None, hax, None)
    rf = pspec.constrain(r.astype(jnp.float32), spec)
    kf = pspec.constrain(k.astype(jnp.float32), spec)
    vf = pspec.constrain(v.astype(jnp.float32), spec)
    w = pspec.constrain(w, spec)

    chunk = min(CHUNK, s)
    if s % chunk == 0 and s > 1:
        mesh = pspec.get_mesh()
        if mesh is not None and bax is not None and hax is not None:
            # WKV is pointwise across batch and heads: shard_map pins the
            # layout (batch on data, heads on model) and runs fully LOCAL —
            # GSPMD propagation otherwise flips the stream batch-replicated
            # (measured 8 GiB unsharded f32 buffers per device; SS Perf)
            spec = P(bax, None, hax, None)
            local = jax.shard_map(
                lambda r_, k_, v_, w_, u_: _wkv_chunked(r_, k_, v_, w_, u_,
                                                        chunk, None, None),
                mesh=mesh, in_specs=(spec, spec, spec, spec, P(hax, None)),
                out_specs=spec, check_vma=False)
            ys = local(rf, kf, vf, w, u)
        else:
            ys = _wkv_chunked(rf, kf, vf, w, u, chunk, bax, hax)  # (B,S,H,hd)
        y = ys.astype(x.dtype).reshape(b, s, d)
    else:
        def step(state, inputs):
            rt, kt, vt, wt = inputs               # (B,H,hd) each
            kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
            y = jnp.einsum("bhk,bhkv->bhv", rt,
                           state + u[None, :, :, None] * kv)
            new_state = state * wt[..., None] + kv
            return new_state, y

        s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
        _, ys = jax.lax.scan(
            step, s0,
            (jnp.moveaxis(rf, 1, 0), jnp.moveaxis(kf, 1, 0),
             jnp.moveaxis(vf, 1, 0), jnp.moveaxis(w, 1, 0)),
        )
        y = jnp.moveaxis(ys, 0, 1).reshape(b, s, d).astype(x.dtype)
    y = _group_norm(y, params["ln_scale"], h) * g
    return y @ params["wo"]




def _wkv_chunked(r, k, v, w, u, chunk, bax, hax):
    """Factorized chunked WKV (GLA-style block decomposition) — the TPU-native
    formulation: per-token state updates become batched einsums over chunks,
    cutting HBM state traffic by ~chunk x (a per-step scan rewrites the
    (B,H,K,V) state every token: ~TBs per training step at 4k).

    With per-channel log-decay ld and inclusive cumsum L_t within a chunk:
      y_t = r_t . (S_chunk + sum_{s<t} exp(L_{t-1}-L_s) k_s v_s + u.k_t v_t)
      S_next = exp(L_C) S_chunk + sum_s exp(L_C - L_s) k_s v_s
    Factorization: scores_ts = (r_t exp(L_{t-1})) . (k_s exp(-L_s)); the only
    positive exponent exp(-L_s) is bounded by chunk*|LOG_DECAY_FLOOR| <= 64,
    safe in f32 for chunk = 32.

    r,k,v: (B,S,H,hd) f32; w: (B,S,H,hd) decay in (0,1). Returns (B,S,H,hd).
    """
    b, s, h, hd = r.shape
    nc = s // chunk

    def c_(t):  # (B,S,H,hd) -> (B,nc,C,H,hd)
        return t.reshape(b, nc, chunk, h, hd)

    rc, kc, vc = c_(r), c_(k), c_(v)
    ld = jnp.log(jnp.maximum(c_(w), 1e-38))              # <= 0
    lcum = jnp.cumsum(ld, axis=2)                        # inclusive (B,nc,C,H,K)
    lprev = lcum - ld                                    # exclusive

    a_fac = rc * jnp.exp(lprev)                          # bounded <= |r|
    b_fac = kc * jnp.exp(-lcum)                          # bounded by chunk*floor
    scores = jnp.einsum("znthk,znshk->znhts", a_fac, b_fac)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), k=-1)
    scores = jnp.where(tri[None, None, None], scores, 0.0)
    scores = pspec.constrain(scores, P(bax, None, hax, None, None))
    y_intra = jnp.einsum("znhts,znshv->znthv", scores, vc)
    # current-token bonus (diagonal)
    diag = jnp.einsum("znthk,znthk->znth", rc, u[None, None, None] * kc)
    y_intra = y_intra + diag[..., None] * vc

    # inter-chunk: carry state (B,H,K,V)
    tail = jnp.exp(lcum[:, :, -1:, :, :] - lcum)         # exp(L_C - L_s) <= 1
    chunk_kv = jnp.einsum("znshk,znshv->znhkv", kc * tail, vc)
    total = jnp.exp(lcum[:, :, -1])                      # (B,nc,H,K)

    def carry(state, inputs):
        ckv, tot = inputs
        prev = state
        state = state * tot[..., None] + ckv
        return state, prev

    s0 = pspec.constrain(jnp.zeros((b, h, hd, hd), jnp.float32),
                         P(bax, hax, None, None))
    _, s_prev = jax.lax.scan(
        carry, s0, (jnp.moveaxis(chunk_kv, 1, 0), jnp.moveaxis(total, 1, 0)))
    s_prev = jnp.moveaxis(s_prev, 0, 1)                  # (B,nc,H,K,V)
    y_inter = jnp.einsum("znthk,znhkv->znthv", a_fac, s_prev)
    out = (y_intra + y_inter).reshape(b, s, h, hd)
    return pspec.constrain(out, P(bax, None, hax, None))


def init_rwkv_cmix(rng, cfg: ModelConfig):
    d, ff = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 4)
    return {
        "mu_k": jnp.full((d,), 0.5, dt),
        "mu_r": jnp.full((d,), 0.5, dt),
        "wk": dense_init(ks[0], d, ff, dt),
        "wv": dense_init(ks[1], ff, d, dt),
        "wr": dense_init(ks[2], d, d, dt),
    }


def rwkv_cmix(params, x, x_prev_last=None):
    b, s, d = x.shape
    if x_prev_last is None:
        x_prev_last = jnp.zeros((b, 1, d), x.dtype)
    x_prev = jnp.concatenate([x_prev_last, x[:, :-1]], axis=1)
    xk = x + (x_prev - x) * params["mu_k"]
    xr = x + (x_prev - x) * params["mu_r"]
    k = jnp.square(jax.nn.relu(xk @ params["wk"]))
    return jax.nn.sigmoid(xr @ params["wr"]) * (k @ params["wv"])


def init_rwkv_cache(cfg: ModelConfig, batch: int, dtype):
    h, hd = rwkv_dims(cfg)
    d = cfg.d_model
    return {
        "state": jnp.zeros((batch, h, hd, hd), jnp.float32),
        "tmix_prev": jnp.zeros((batch, 1, d), dtype),
        "cmix_prev": jnp.zeros((batch, 1, d), dtype),
    }


def rwkv_tmix_decode(params, cfg: ModelConfig, x, cache):
    """x: (B, 1, d). Returns (y, new_cache-fragment)."""
    b, _, d = x.shape
    h, hd = rwkv_dims(cfg)
    mixed = _token_shift(params, x, cache["tmix_prev"])
    r = (mixed["r"] @ params["wr"]).reshape(b, h, hd).astype(jnp.float32)
    k = (mixed["k"] @ params["wk"]).reshape(b, h, hd).astype(jnp.float32)
    v = (mixed["v"] @ params["wv"]).reshape(b, h, hd).astype(jnp.float32)
    g = jax.nn.silu(mixed["g"] @ params["wg"])
    w = _decay(params, mixed["w"]).reshape(b, h, hd)
    u = params["u"].reshape(h, hd)
    kv = jnp.einsum("bhk,bhv->bhkv", k, v)
    y = jnp.einsum("bhk,bhkv->bhv", r, cache["state"] + u[None, :, :, None] * kv)
    new_state = cache["state"] * w[..., None] + kv
    y = y.reshape(b, 1, d).astype(x.dtype)
    y = _group_norm(y, params["ln_scale"], h) * g
    return y @ params["wo"], {"state": new_state, "tmix_prev": x}
