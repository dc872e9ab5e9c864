"""Data and the plain reference for the tabular GAL cells.

The data follows ``data/synthetic.make_regression``'s distribution (copied
here, not imported): x ~ N(0, 1), label weights w ~ N(0, 1), and
y = x @ w + 0.2 sin(2 x_0) |x_1| + 0.3 N(0, 1). The D columns are split into
M contiguous blocks of D / M, one per organization, as
``data/partition.split_features`` does without a permutation. Everything
is drawn on the device from the seed in one jitted call; the host never
holds the features.

The reference is GAL (Diao, Ding and Tarokh, NeurIPS 2022, Algorithm 1)
for mse with ridge-regression organizations, written out plainly: per round
the residual, each organization's ridge solve from its normal equations,
Alice's assistance weights by Adam on softmax logits, the assisted
learning rate by the secant iteration on the (here quadratic) loss, and the
ensemble update. It imports nothing of the program and takes nothing the
program made: the seed-derived round keys (which seed the weight fit's
starting jitter) are part of the protocol's inputs. The weight objective and
the line search are evaluated from exact sufficient statistics (the
predictions' Gram matrix and inner products), so the reference costs a few
passes over the features.

``precision="bfloat16"`` is the control: every matrix product of the
reference computed in one bfloat16 pass (operands rounded to bfloat16,
products accumulated in f32), the precision one step below the
configuration's ``high`` (bfloat16 x3).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import gal_ref

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A legacy uint32[2] PRNG key from any non-negative integer seed,
    including seeds wider than 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("n", "d", "m", "n_test"))
def make_data(key, *, n: int, d: int, m: int, n_test: int,
              noise: float = 0.3, nonlinear: float = 0.2):
    """Per-organization train and held-out slices and labels, drawn on the
    device. Returns (xs, y, xs_test, y_test); xs[i] is (n, d // m)."""
    if d % m:
        raise ValueError(f"{d} features do not split evenly over {m} orgs")
    dm = d // m
    k_x, k_t, k_w, k_n, k_nt = jax.random.split(key, 5)
    w = jax.random.normal(k_w, (m, dm, 1), jnp.float32)

    def side(k, rows, k_noise):
        xs = [jax.random.normal(jax.random.fold_in(k, i), (rows, dm),
                                jnp.float32) for i in range(m)]
        y = sum(jnp.dot(xs[i], w[i], precision=HIGHEST) for i in range(m))
        y = y + nonlinear * jnp.sin(2.0 * xs[0][:, :1]) * jnp.abs(
            xs[0][:, 1:2])
        y = y + noise * jax.random.normal(k_noise, (rows, 1), jnp.float32)
        return xs, y

    xs, y = side(k_x, n, k_n)
    xs_t, y_t = side(k_t, n_test, k_nt)
    return xs, y, xs_t, y_t


def matmul(a, b, precision: str):
    """a @ b at "highest" (f32) or "bfloat16" (one bfloat16 pass, f32
    accumulation)."""
    if precision == "highest":
        return jnp.dot(a, b, precision=HIGHEST)
    if precision == "bfloat16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    raise ValueError(f"unknown reference precision {precision!r}")


@partial(jax.jit, static_argnames=("precision",))
def _gram(x, precision):
    """Normal-equation matrix of [x, 1]: (d + 1, d + 1)."""
    n = x.shape[0]
    xtx = matmul(x.T, x, precision)
    s = matmul(jnp.ones((1, n), jnp.float32), x, precision)     # (1, d)
    top = jnp.concatenate([xtx, s.T], axis=1)
    bottom = jnp.concatenate([s, jnp.full((1, 1), n, jnp.float32)], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


@partial(jax.jit, static_argnames=("precision",))
def _ridge_round(x, gram, r, ridge, precision):
    """One organization's ridge fit of r: coefficients (d, 1), bias (1,),
    and its fitted values (n,)."""
    n, d = x.shape
    rhs = jnp.concatenate([
        matmul(x.T, r, precision),
        matmul(jnp.ones((1, n), jnp.float32), r, precision)], axis=0)
    sol = jnp.linalg.solve(gram + ridge * jnp.eye(d + 1, dtype=jnp.float32),
                           rhs)
    coef, bias = sol[:-1], sol[-1]
    return coef, bias, (matmul(x, coef, precision) + bias)[:, 0]


@partial(jax.jit, static_argnames=("epochs", "precision"))
def _weights_and_direction(k_round, preds, r, *, epochs: int, lr: float,
                           weight_decay: float, precision: str):
    """Alice's assistance weights and the weighted direction (n,)."""
    m, n = preds.shape
    w = gal_ref.weight_fit(
        jax.random.fold_in(k_round, 29), matmul(preds, preds.T, precision),
        matmul(preds, r, precision)[:, 0], matmul(r.T, r, precision)[0, 0],
        n, epochs=epochs, lr=lr, weight_decay=weight_decay)
    return w, matmul(w[None, :], preds, precision)[0]


@partial(jax.jit, static_argnames=("precision",))
def _eta(r, direction, precision):
    """argmin over eta of 0.5 mean((r - eta d)^2), a quadratic whose value
    and slope come from r.r, r.d and d.d."""
    n = r.shape[0]
    d = direction[:, None]
    rd = matmul(r.T, d, precision)[0, 0]
    dd = matmul(d.T, d, precision)[0, 0]
    rr = matmul(r.T, r, precision)[0, 0]
    return gal_ref.secant_eta(
        lambda e: 0.5 * (rr - 2.0 * e * rd + e * e * dd) / n,
        lambda e: (e * dd - rd) / n)


def reference_fit(key, xs: Sequence[jax.Array], y: jax.Array, *,
                  rounds: int, ridge: float, weight_epochs: int,
                  weight_lr: float, weight_decay: float,
                  precision: str = "highest") -> Dict[str, Any]:
    """GAL for mse with ridge organizations, plainly. ``key`` is the fit's
    key; round t draws from the t-th split of the chain, as the protocol
    does. Returns etas (T,), weights (T, M) and per-round coefficients."""
    y = y.astype(jnp.float32)
    f0 = jnp.mean(y, axis=0)                               # (1,)
    f = jnp.broadcast_to(f0, y.shape)
    grams = [_gram(x, precision) for x in xs]
    etas, weights, coefs = [], [], []
    for _ in range(rounds):
        key, k_round = jax.random.split(key)
        r = y - f                                          # (n, 1)
        fits = [_ridge_round(x, gm, r, ridge, precision)
                for x, gm in zip(xs, grams)]
        preds = jnp.stack([p for _, _, p in fits])         # (m, n)
        w, direction = _weights_and_direction(
            k_round, preds, r, epochs=weight_epochs, lr=weight_lr,
            weight_decay=weight_decay, precision=precision)
        del preds
        eta = _eta(r, direction, precision)
        f = f + eta * direction[:, None]
        etas.append(eta)
        weights.append(w)
        coefs.append([(c, b) for c, b, _ in fits])
    return {"f0": f0, "etas": jnp.stack(etas), "weights": jnp.stack(weights),
            "coefs": coefs, "precision": precision}


def reference_predict(ref: Dict[str, Any], xs: Sequence[jax.Array]
                      ) -> jax.Array:
    """The prediction stage: F0 + sum_t eta_t sum_m w_tm f_tm(x_m), (n,)."""
    p = ref["precision"]
    out = jnp.broadcast_to(ref["f0"], (xs[0].shape[0],)).astype(jnp.float32)
    for t, fits in enumerate(ref["coefs"]):
        for i, (c, b) in enumerate(fits):
            out = out + ref["etas"][t] * ref["weights"][t, i] * (
                matmul(xs[i], c, p)[:, 0] + b)
    return out


def reference_local_fits(ref: Dict[str, Any], xs: Sequence[jax.Array]
                         ) -> jax.Array:
    """Each organization's round-0 fitted values on xs, (M, n)."""
    p = ref["precision"]
    return jnp.stack([matmul(x, c, p)[:, 0] + b
                      for x, (c, b) in zip(xs, ref["coefs"][0])])


def _widest(a, ref) -> float:
    """The widest gap of a from ref, relative to ref's root mean square."""
    return float(np.max(np.abs(a - ref)) / np.sqrt(np.mean(ref ** 2)))


def gaps(etas, weights, preds, local, ref_etas, ref_weights, ref_preds,
         ref_local) -> Dict[str, float]:
    """The gaps of a fit from the reference. Compared: the first round's
    eta (relative); each organization's round-0 fitted values on the
    held-out rows (``local``, (M, n)), by the widest gap of the worst
    organization relative to its reference values' root mean square; and
    the same widest gap of the held-out predictions. Logged and not
    compared: the first round's assistance weights (absolute; they lie on
    the simplex) and ``all_rounds_*``, the widest eta and weight gaps over
    every round. Adam's sign-like steps make the weights swing by a few
    1e-4 with any rounding, which moves eta and, over the later rounds
    that fit a residual that is mostly noise, the predictions; the round-0
    local fits come before the weight fit and do not swing."""
    etas = np.asarray(etas, np.float64)
    ref_etas = np.asarray(ref_etas, np.float64)
    weights = np.asarray(weights, np.float64)
    ref_weights = np.asarray(ref_weights, np.float64)
    preds = np.asarray(preds, np.float64).reshape(-1)
    ref_preds = np.asarray(ref_preds, np.float64).reshape(-1)
    local = np.asarray(local, np.float64)
    ref_local = np.asarray(ref_local, np.float64)
    if (etas.shape != ref_etas.shape or weights.shape != ref_weights.shape
            or preds.shape != ref_preds.shape
            or local.shape != ref_local.shape):
        return dict.fromkeys(("round0_eta_gap", "round0_weight_gap",
                              "local_fit_gap", "pred_gap",
                              "all_rounds_eta_gap", "all_rounds_weight_gap"),
                             float("inf"))
    eta_rel = np.abs(etas - ref_etas) / np.abs(ref_etas)
    w_abs = np.max(np.abs(weights - ref_weights), axis=1)
    return {
        "round0_eta_gap": float(eta_rel[0]),
        "round0_weight_gap": float(w_abs[0]),
        "local_fit_gap": max(_widest(a, r) for a, r in zip(local, ref_local)),
        "pred_gap": _widest(preds, ref_preds),
        "all_rounds_eta_gap": float(np.max(eta_rel)),
        "all_rounds_weight_gap": float(np.max(w_abs)),
    }
