"""Overarching losses L1 and local regression losses ell_m (paper Sec. 3.2).

Conventions (matching gradient boosting, to which GAL reduces for M=1):
  * F lives in *link space*: raw logits for classification, raw output for
    regression. y is one-hot (N, K) for K-class tasks, (N, 1) for regression
    and binary tasks.
  * ``residual(y, F)`` is the per-sample pseudo-residual
        r = -dL(y, F)/dF     (no 1/N factor; the N-mean lives in the loss)
    which is the tensor Alice broadcasts each assistance round.
  * ``init_prediction(y)`` gives F^0: E_N(y) mapped to link space (the paper's
    deterministic unbiased initializer, Appendix A.1).

Local losses ell_q(r, f) = mean |r - f|^q  (paper Table 4, q in {1,1.5,2,4}).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.utils.registry import Registry

LOSSES: Registry = Registry("loss")


@dataclass(frozen=True)
class Loss:
    name: str

    def __call__(self, y, f):  # mean scalar loss
        raise NotImplementedError

    def residual(self, y, f):  # per-sample -dL/dF
        # generic fallback: autodiff of the summed loss. Pure lax, so a
        # custom Loss subclass that only defines per_sample compiles
        # straight into the fused engines' scanned round step — no Python
        # fallback for autodiff-residual losses.
        return -jax.grad(lambda ff: jnp.sum(self.per_sample(y, ff)))(f)

    def per_sample(self, y, f):
        raise NotImplementedError

    def init_prediction(self, y):
        raise NotImplementedError


def autodiff_residual(loss: Loss, y, f):
    """The generic ``-dL/dF`` fallback of ``Loss.residual``, bypassing any
    closed form the subclass defines. This is the oracle the closed forms
    and the Pallas ``residual_xent`` kernel are validated against
    (``tests/test_kernels.py``), and what a custom loss gets for free
    inside the compiled engines."""
    return Loss.residual(loss, y, f)


# vocab width from which CrossEntropyLoss.residual routes through the fused
# Pallas kernel (kernels/residual_xent.py): below this a second (N, K)
# softmax buffer is cheap; at LM scale the kernel streams vocab tiles
# through VMEM instead of materializing softmax(F) in HBM.
XENT_KERNEL_MIN_CLASSES = 1024
# backends where the automatic route engages. Elsewhere (CPU/GPU) the
# kernel would run in interpret mode — Python-emulated, far slower than the
# closed form — or fail to lower, so the closed form stays the default;
# tests widen this to exercise the dispatch in interpret mode.
XENT_KERNEL_BACKENDS = ("tpu",)


@LOSSES.register("mse")
@dataclass(frozen=True)
class MSELoss(Loss):
    name: str = "mse"

    def per_sample(self, y, f):
        return 0.5 * jnp.sum(jnp.square(y - f), axis=-1)

    def __call__(self, y, f):
        return jnp.mean(self.per_sample(y, f))

    def residual(self, y, f):
        return y - f

    def init_prediction(self, y):
        return jnp.mean(y, axis=0, keepdims=True)


@LOSSES.register("mae")
@dataclass(frozen=True)
class MAELoss(Loss):
    """Mean absolute deviation (the paper's regression metric and an L1 choice)."""
    name: str = "mae"

    def per_sample(self, y, f):
        return jnp.sum(jnp.abs(y - f), axis=-1)

    def __call__(self, y, f):
        return jnp.mean(self.per_sample(y, f))

    def residual(self, y, f):
        return jnp.sign(y - f)

    def init_prediction(self, y):
        return jnp.median(y, axis=0, keepdims=True)


@LOSSES.register("xent")
@dataclass(frozen=True)
class CrossEntropyLoss(Loss):
    """K-class cross entropy on logits; r = y - softmax(F) (Friedman
    multiclass). At LM scale (K >= ``XENT_KERNEL_MIN_CLASSES``, a
    ``XENT_KERNEL_BACKENDS`` backend) the residual routes through the
    fused Pallas kernel ``kernels/residual_xent.py`` automatically — the
    broadcast tensor is GAL's protocol hot path, and the kernel streams
    vocab tiles through VMEM instead of materializing softmax(F) as a
    second (N, K) buffer. The kernel recovers labels via argmax, so the
    route adds the correction term ``y - onehot(argmax(y))`` — exactly
    zero for one-hot y and exactly the smoothing mass for soft targets,
    keeping both conventions exact on every backend."""
    name: str = "xent"

    def per_sample(self, y, f):
        return -jnp.sum(y * jax.nn.log_softmax(f, axis=-1), axis=-1)

    def __call__(self, y, f):
        return jnp.mean(self.per_sample(y, f))

    def residual(self, y, f):
        if (f.ndim == 2 and y.shape == f.shape
                and f.shape[-1] >= XENT_KERNEL_MIN_CLASSES
                and jax.default_backend() in XENT_KERNEL_BACKENDS):
            # static shape+backend gate: trace-safe, picked up inside the
            # fused round scan with no engine involvement. The kernel
            # recovers labels via argmax, so
            #   r = y - softmax
            #     = (onehot(argmax y) - softmax)   <- the kernel
            #     + (y - onehot(argmax y))         <- zero for one-hot y
            # and soft/smoothed targets stay exact too; the correction is
            # a fused elementwise term, no extra softmax buffer.
            from repro.kernels.ops import residual_xent
            labels = jnp.argmax(y, axis=-1)
            hard = jax.nn.one_hot(labels, f.shape[-1], dtype=y.dtype)
            return residual_xent(f, labels) + (y - hard)
        return y - jax.nn.softmax(f, axis=-1)

    def init_prediction(self, y):
        prior = jnp.clip(jnp.mean(y, axis=0, keepdims=True), 1e-6, 1.0)
        return jnp.log(prior)


@LOSSES.register("bce")
@dataclass(frozen=True)
class BCELoss(Loss):
    """Binary cross entropy on a single logit (imbalanced tasks, MIMICM-like)."""
    name: str = "bce"

    def per_sample(self, y, f):
        return jnp.sum(
            jnp.maximum(f, 0.0) - f * y + jnp.log1p(jnp.exp(-jnp.abs(f))), axis=-1
        )

    def __call__(self, y, f):
        return jnp.mean(self.per_sample(y, f))

    def residual(self, y, f):
        return y - jax.nn.sigmoid(f)

    def init_prediction(self, y):
        p = jnp.clip(jnp.mean(y, axis=0, keepdims=True), 1e-6, 1 - 1e-6)
        return jnp.log(p / (1 - p))


def lq_loss(q: float):
    """Local regression loss ell_q(r, f) = mean |r - f|^q (paper Table 4).

    Equal exponents return the same function, so a compiled round program,
    which holds each group's local loss in its signature, is found again
    by the next fit of freshly built organizations."""
    return _lq_loss(float(q))


@lru_cache(maxsize=64)
def _lq_loss(q: float):
    def loss(r, f):
        d = jnp.abs(r - f)
        if q == 2.0:
            return jnp.mean(jnp.square(d))
        if q == 1.0:
            # smooth |.| for stable autodiff at 0
            return jnp.mean(jnp.sqrt(jnp.square(d) + 1e-12))
        return jnp.mean(jnp.power(d + 1e-12, q))

    loss.q = q
    loss.__name__ = f"l{q:g}"
    return loss


def get_loss(name: str) -> Loss:
    cls = LOSSES.get(name)
    return cls() if isinstance(cls, type) else cls
