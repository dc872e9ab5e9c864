"""Tokens, weights and the plain reference for the LM-scale GAL cells.

Tokens follow ``data/tokens.make_token_stream``'s distribution (copied, not
imported): a hidden Markov chain over 8 states with Dirichlet(0.5)
transitions, each state emitting uniformly from its own band of the vocab.
Each of the B rows is a window of one stream; labels are the next tokens.
Organization m sees the private view of the tokens the smoke run used: the
high or the low digits of the token id in base floor(sqrt(V)).

The weights are made here, on the device from the seed in one jitted call,
in the layout and dtype the program serves them in, and the program and
the reference both start from them.

The reference is the same collaboration written out plainly in float32
at the highest matmul precision: a pre-norm decoder (LayerNorm with bias;
rotary embedding on the leading ``rotary_fraction`` of each head, its
halves rotated; causal softmax attention; SwiGLU MLP; untied output head),
each organization's local fit as AdamW steps (no weight decay) on
mean((f(x_m) - r)^2), and steps 4 and 5 from ``gal_ref``. The LayerNorm
eps and the rotary fraction are the configuration's; the configuration
file lists where they, and the projections' bias, depart from the
published model.
``precision="float8"`` is the control: every matrix product with its
operands rounded to float8 (e4m3), products accumulated in f32.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import gal_ref

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b, precision: str):
    """Matrix product over the last axis of a and the first of b."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision == "float8":
        f8 = jnp.float8_e4m3fn
        return jnp.matmul(a.astype(f8), b.astype(f8),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown reference precision {precision!r}")


@partial(jax.jit, static_argnames=("vocab", "batch", "seq", "n_states"))
def make_tokens(key, *, vocab: int, batch: int, seq: int, n_states: int = 8):
    """(tokens, labels), each (batch, seq) int32."""
    k_t, k_s, k_o, k_w = jax.random.split(key, 4)
    trans = jax.random.dirichlet(k_t, jnp.full((n_states,), 0.5),
                                 (n_states,))
    length = 4 * batch * seq + 2
    band = vocab // n_states

    def step(state, k):
        nxt = jax.random.categorical(k, jnp.log(trans[state]))
        return nxt, state

    s0 = jax.random.randint(k_s, (), 0, n_states)
    _, states = jax.lax.scan(step, s0, jax.random.split(k_s, length))
    offsets = jax.random.randint(k_o, (length,), 0, max(band, 1))
    stream = (states * band + offsets) % vocab
    starts = jax.random.randint(k_w, (batch,), 0, length - seq - 1)
    rows = starts[:, None] + jnp.arange(seq)[None, :]
    return (stream[rows].astype(jnp.int32),
            stream[rows + 1].astype(jnp.int32))


def view_fns(vocab: int, m: int):
    """Organization m's private view of the tokens."""
    root = int(np.sqrt(vocab))
    fns = [lambda t: (t // root) % vocab, lambda t: (t % root) % vocab]
    if m > len(fns):
        raise ValueError(f"{m} organizations, {len(fns)} token views")
    return fns[:m]


def views(tokens, vocab: int, m: int):
    """The M private views of the tokens, stacked (M, B, S)."""
    return jnp.stack([fn(tokens) for fn in view_fns(vocab, m)])


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@partial(jax.jit, static_argnames=("dims", "m"))
def init_weights(key, *, dims, m: int):
    """M organizations' weights, each in the program's parameter layout:
    layers stacked on a leading axis, matrices in the compute dtype,
    norm parameters in f32."""
    (n_layers, d, heads, hd, ff, vocab, dtype) = dims
    dt = jnp.dtype(dtype)

    def one(k):
        ks = jax.random.split(k, 9)
        ones = jnp.ones((n_layers, d), jnp.float32)
        zeros = jnp.zeros((n_layers, d), jnp.float32)
        return {
            "embed": {"tok": _normal(ks[0], (vocab, d), 0.02, dt),
                      "unembed": _normal(ks[1], (d, vocab), d ** -0.5, dt)},
            "ln_f": {"scale": jnp.ones((d,), jnp.float32),
                     "bias": jnp.zeros((d,), jnp.float32)},
            "layers": {
                "ln1": {"scale": ones, "bias": zeros},
                "ln2": {"scale": ones, "bias": zeros},
                "attn": {
                    "wq": _normal(ks[2], (n_layers, d, heads * hd),
                                  d ** -0.5, dt),
                    "wk": _normal(ks[3], (n_layers, d, heads * hd),
                                  d ** -0.5, dt),
                    "wv": _normal(ks[4], (n_layers, d, heads * hd),
                                  d ** -0.5, dt),
                    "wo": _normal(ks[5], (n_layers, heads * hd, d),
                                  (heads * hd) ** -0.5, dt)},
                "mlp": {
                    "w_gate": _normal(ks[6], (n_layers, d, ff), d ** -0.5,
                                      dt),
                    "w_up": _normal(ks[7], (n_layers, d, ff), d ** -0.5, dt),
                    "w_down": _normal(ks[8], (n_layers, ff, d), ff ** -0.5,
                                      dt)}},
        }

    return [one(jax.random.fold_in(key, i)) for i in range(m)]


# ---------------------------------------------------------------- reference

def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotary(x, theta, fraction):
    """x (B, S, H, hd): rotate the two halves of the leading
    ``fraction`` of each head; the rest passes through."""
    s, rot = x.shape[1], int(x.shape[-1] * fraction)
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, rot/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def forward(p, tokens, *, arch, precision: str):
    """Logits (B, S, V) of one organization, float32. ``arch`` is
    (heads, rope theta, rotary fraction, LayerNorm eps)."""
    heads, theta, fraction, eps = arch
    b, s = tokens.shape
    x = p["embed"]["tok"][tokens]
    mask = jnp.tril(jnp.ones((s, s), bool))
    n_layers = p["layers"]["attn"]["wq"].shape[0]
    for i in range(n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], p["layers"])
        h = _layernorm(x, lp["ln1"], eps)
        q = mm(h, lp["attn"]["wq"], precision).reshape(b, s, heads, -1)
        k = mm(h, lp["attn"]["wk"], precision).reshape(b, s, heads, -1)
        v = mm(h, lp["attn"]["wv"], precision).reshape(b, s, heads, -1)
        q, k = _rotary(q, theta, fraction), _rotary(k, theta, fraction)
        hd = q.shape[-1]
        scores = jnp.einsum("bshd,bthd->bhst", q, k,
                            precision=HIGHEST) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        att = jnp.einsum("bhst,bthd->bshd", probs, v, precision=HIGHEST)
        x = x + mm(att.reshape(b, s, -1), lp["attn"]["wo"], precision)
        h = _layernorm(x, lp["ln2"], eps)
        g = jax.nn.silu(mm(h, lp["mlp"]["w_gate"], precision))
        x = x + mm(g * mm(h, lp["mlp"]["w_up"], precision),
                   lp["mlp"]["w_down"], precision)
    x = _layernorm(x, p["ln_f"], eps)
    return mm(x, p["embed"]["unembed"], precision)


@partial(jax.jit, static_argnames=("arch", "steps", "precision"))
def local_fit(p, opt, view, residual, *, arch, steps: int, lr: float,
              precision: str):
    """An organization's round: AdamW steps on mean((f - r)^2), its moments
    and step count carried from round to round, then its fitted logits
    (B*S, V)."""
    def loss(q):
        f = forward(q, view, arch=arch, precision=precision)
        return jnp.mean(jnp.square(f - residual))

    b1, b2, eps = 0.9, 0.999, 1e-8
    mom, vel, count = opt
    for _ in range(steps):
        g = jax.grad(loss)(p)
        count = count + 1
        k = count.astype(jnp.float32)
        mom = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b,
                                     mom, g)
        vel = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                                     vel, g)
        p = jax.tree_util.tree_map(
            lambda w, a, c: w - lr * (a / (1 - b1 ** k)) / (
                jnp.sqrt(c / (1 - b2 ** k)) + eps), p, mom, vel)
    f = forward(p, view, arch=arch, precision=precision)
    return p, (mom, vel, count), f.reshape(-1, f.shape[-1])


def _xent(y1, f):
    return jnp.mean(-jnp.sum(y1 * jax.nn.log_softmax(f, axis=-1), -1))


@partial(jax.jit, static_argnames=("epochs",))
def _assist(k_round, preds, residual, f, y1, *, epochs: int):
    """Steps 4 to 6 of one round: weights, eta, the new ensemble."""
    m = preds.shape[0]
    flat = preds.reshape(m, -1)
    r = residual.reshape(-1)
    w = gal_ref.weight_fit(
        jax.random.fold_in(k_round, 29),
        jnp.matmul(flat, flat.T, precision=HIGHEST),
        jnp.matmul(flat, r, precision=HIGHEST),
        jnp.dot(r, r, precision=HIGHEST), r.size, epochs=epochs, lr=0.1,
        weight_decay=5e-4)
    direction = jnp.einsum("m,mnv->nv", w, preds, precision=HIGHEST)
    phi = lambda e: _xent(y1, f + e * direction)              # noqa: E731
    eta = gal_ref.secant_eta(phi, jax.grad(phi))
    return w, eta, f + eta * direction


def reference_fit(key, weights: List[Any], view_stack, labels, *,
                  rounds: int, local_steps: int, lr: float, arch,
                  weight_epochs: int,
                  precision: str = "highest") -> Dict[str, Any]:
    """The LM collaboration, plainly. Round t draws its weight-fit jitter
    from fold_in(key, t); ``arch`` is as ``forward`` takes it. Returns
    etas (T,), weights (T, M), the final ensemble logits F (B*S, V), the
    initial F0 (1, V) and each organization's round-0 fitted logits
    (M, B*S, V)."""
    vocab = weights[0]["embed"]["unembed"].shape[-1]
    b, s = labels.shape
    y1 = jax.nn.one_hot(labels.reshape(-1), vocab, dtype=jnp.float32)
    f0 = jnp.log(jnp.clip(jnp.mean(y1, axis=0, keepdims=True), 1e-6, 1.0))
    f = jnp.broadcast_to(f0, (b * s, vocab))
    params = [jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
              for p in weights]
    zeros = lambda p: jax.tree_util.tree_map(jnp.zeros_like, p)  # noqa: E731
    opts = [(zeros(p), zeros(p), jnp.zeros((), jnp.int32)) for p in params]
    etas, ws = [], []
    for t in range(rounds):
        residual = y1 - jax.nn.softmax(f, axis=-1)
        preds = []
        for i in range(len(params)):
            params[i], opts[i], fi = local_fit(
                params[i], opts[i], view_stack[i],
                residual.reshape(b, s, vocab),
                arch=arch, steps=local_steps, lr=lr,
                precision=precision)
            preds.append(fi)
        if t == 0:
            local0 = jnp.stack(preds)
        w, eta, f = _assist(jax.random.fold_in(key, t), jnp.stack(preds),
                            residual, f, y1, epochs=weight_epochs)
        del preds
        etas.append(eta)
        ws.append(w)
    return {"etas": jnp.stack(etas), "weights": jnp.stack(ws), "f": f,
            "f0": f0, "local0": local0}


def _rms(a):
    return jnp.sqrt(jnp.mean(jnp.square(a)))


def gaps(etas, weights, f, local, ref) -> Dict[str, float]:
    """The gaps of an LM fit from the reference. Compared: each
    organization's round-0 fitted logits (``local``, (M, B*S, V)), by the
    worst organization's root-mean-square gap relative to the root mean
    square of its reference logits (``local_logit_gap``) and by the widest
    such gap of one token's logits (``local_token_gap``), so that one
    token's answer gone wrong shows. Logged and not compared: the first
    round's eta (relative) and weights (absolute), and the final ensemble
    logits F against the reference's, relative to the root mean square of
    the reference's change F - F0 (``logit_gap``, and ``token_gap`` for
    the widest token). The line search's eta swings from seed to seed
    with the rounding of the fitted logits (its first-round gap reads 0.01
    to 0.6 in sound runs), and F swings with it; the local fits come
    before it."""
    etas = np.asarray(etas, np.float64)
    weights = np.asarray(weights, np.float64)
    r_etas = np.asarray(ref["etas"], np.float64)
    r_w = np.asarray(ref["weights"], np.float64)
    names = ("round0_eta_gap", "round0_weight_gap", "local_logit_gap",
             "local_token_gap", "logit_gap", "token_gap",
             "all_rounds_eta_gap", "all_rounds_weight_gap")
    if (etas.shape != r_etas.shape or weights.shape != r_w.shape
            or f.shape != ref["f"].shape
            or local.shape != ref["local0"].shape):
        return dict.fromkeys(names, float("inf"))
    scale = _rms(ref["f"] - ref["f0"])
    sq = jnp.square(f.astype(jnp.float32) - ref["f"])
    eta_rel = np.abs(etas - r_etas) / np.abs(r_etas)
    w_abs = np.max(np.abs(weights - r_w), axis=1)
    local_rms, local_tok = [], []
    for mine, theirs in zip(local, ref["local0"]):
        lsq = jnp.square(mine.astype(jnp.float32) - theirs)
        own = _rms(theirs)
        local_rms.append(float(jnp.sqrt(jnp.mean(lsq)) / own))
        local_tok.append(float(jnp.sqrt(jnp.max(jnp.mean(lsq, -1))) / own))
    return {"round0_eta_gap": float(eta_rel[0]),
            "round0_weight_gap": float(w_abs[0]),
            "local_logit_gap": max(local_rms),
            "local_token_gap": max(local_tok),
            "logit_gap": float(jnp.sqrt(jnp.mean(sq)) / scale),
            "token_gap": float(jnp.sqrt(jnp.max(jnp.mean(sq, -1))) / scale),
            "all_rounds_eta_gap": float(np.max(eta_rel)),
            "all_rounds_weight_gap": float(np.max(w_abs))}
