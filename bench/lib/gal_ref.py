"""The plain reference of GAL's steps 4 and 5, shared by every cell's
reference (Diao, Ding and Tarokh, NeurIPS 2022, Algorithm 1; Table 9).

Step 4, Alice's assistance weights: w = softmax(theta) minimizes
mean((r - sum_m w_m f_m)^2), by Adam (coupled L2 weight decay, bias
corrected) from theta_m = 0.01 N(0, 1) drawn per organization from the round
key. The objective is evaluated from its exact sufficient statistics, the
predictions' Gram matrix P P^T, P r and r.r, so each epoch costs O(M^2).

Step 5, the assisted learning rate: the paper's 1-D L-BFGS, which in one
dimension is the secant iteration; Armijo-halved (six trials), its step
clipped to [1e-4, 64] and the iterate to [-64, 64], started at eta = 1
with a previous iterate at 0.5, 25 iterations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def softmax(t):
    e = jnp.exp(t - jnp.max(t))
    return e / jnp.sum(e)


def weight_fit(k_weights, ptp, ptr, rr, count, *, epochs: int, lr: float,
               weight_decay: float):
    """Assistance weights from the statistics of M predictions over
    ``count`` entries: ptp (M, M), ptr (M,), rr ()."""
    m = ptp.shape[0]
    theta = jnp.stack([0.01 * jax.random.normal(
        jax.random.fold_in(k_weights, i), (), jnp.float32)
        for i in range(m)])

    def objective(t):
        w = softmax(t)
        return (rr - 2.0 * jnp.dot(w, ptr, precision=HIGHEST)
                + jnp.dot(w, jnp.dot(ptp, w, precision=HIGHEST),
                          precision=HIGHEST)) / count

    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(carry, i):
        t, mom, vel = carry
        g = jax.grad(objective)(t) + weight_decay * t
        mom = b1 * mom + (1 - b1) * g
        vel = b2 * vel + (1 - b2) * g * g
        k = (i + 1).astype(jnp.float32)
        upd = -lr * (mom / (1 - b1 ** k)) / (
            jnp.sqrt(vel / (1 - b2 ** k)) + eps)
        return (t + upd, mom, vel), None

    zeros = jnp.zeros((m,), jnp.float32)
    (theta, _, _), _ = jax.lax.scan(step, (theta, zeros, zeros),
                                    jnp.arange(epochs))
    return softmax(theta)


def secant_eta(f, g, iters: int = 25, max_range: float = 64.0):
    """argmin of the scalar function ``f`` (gradient ``g``) by the
    safeguarded secant iteration."""
    def body(_, state):
        x_prev, g_prev, x, gx = state
        denom = gx - g_prev
        h = jnp.where(jnp.abs(denom) > 1e-12, (x - x_prev) / denom, 1.0)
        h = jnp.clip(h, 1e-4, max_range)
        x_new = jnp.clip(x - h * gx, -max_range, max_range)
        f_x = f(x)

        def armijo(_, x_try):
            worse = f(x_try) > f_x + 1e-4 * gx * (x_try - x)
            return jnp.where(worse, 0.5 * (x_try + x), x_try)

        x_new = jax.lax.fori_loop(0, 6, armijo, x_new)
        return (x, gx, x_new, g(x_new))

    x0 = jnp.float32(1.0)
    state = (x0 - 0.5, g(x0 - 0.5), x0, g(x0))
    return jax.lax.fori_loop(0, iters, body, state)[2]
