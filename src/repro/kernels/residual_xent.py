"""Fused pseudo-residual kernel: r = onehot(y) - softmax(F), tiled over vocab.

This is GAL's protocol hot tensor at LM scale (DESIGN.md Sec. 5): the residual
Alice broadcasts is (tokens, vocab) with vocab up to 152k. A naive jnp
implementation materializes softmax(F) in HBM (a second vocab-sized tensor)
before subtracting; this kernel streams vocab tiles through VMEM twice:

  pass 1  row stats  — online (max, sumexp) accumulated across vocab tiles
  pass 2  residual   — emit onehot - exp(x - m)/l per tile

Tiles are (BT, BV) = (128, 512): MXU/VPU aligned (multiples of 128), VMEM
footprint ~BT*BV*4B = 256 KiB per ref. The vocab grid dimension is sequential
("arbitrary") so the stats carry is legal; the token dimension is parallel.

Callers: ``repro.kernels.ops.residual_xent`` (the jit'd entry the LM engine
uses) and — automatically — ``CrossEntropyLoss.residual`` for one-hot
targets at vocab >= ``repro.core.losses.XENT_KERNEL_MIN_CLASSES``, so any
GAL engine whose Alice loss is softmax cross entropy picks the kernel up
inside its scanned round step with no configuration.

TPU is the target; correctness is validated with interpret=True on CPU
against both the jnp reference and the generic autodiff ``Loss.residual``
oracle, including tied-max rows spanning tile seams and the -inf padded
vocab tail (``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BT = 128   # token-block rows
BV = 512   # vocab-block cols
NEG_INF = -1e30


def _stats_kernel(x_ref, m_ref, l_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    x = x_ref[...].astype(jnp.float32)
    m_prev = m_ref[...]                                  # (BT, 1)
    blk_max = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, blk_max)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(
        jnp.exp(x - m_new), axis=-1, keepdims=True)
    m_ref[...] = m_new


def _resid_kernel(x_ref, lab_ref, m_ref, l_ref, out_ref):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    sm = jnp.exp(x - m_ref[...]) / jnp.maximum(l_ref[...], 1e-30)
    cols = j * BV + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (lab_ref[...] == cols).astype(jnp.float32)
    out_ref[...] = (onehot - sm).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def residual_xent_kernel(logits: jnp.ndarray, labels: jnp.ndarray,
                         interpret: bool = True,
                         out_dtype=jnp.float32) -> jnp.ndarray:
    """logits: (T, V); labels: (T,) int32 -> residual (T, V) out_dtype.

    Pads T to BT and V to BV multiples (pad logits with -inf so softmax is
    unaffected; pad labels with -1 which never matches a column).
    """
    t, v = logits.shape
    tp = -(-t // BT) * BT
    vp = -(-v // BV) * BV
    x = jnp.pad(logits, ((0, tp - t), (0, vp - v)),
                constant_values=NEG_INF)
    lab = jnp.pad(labels.astype(jnp.int32), (0, tp - t),
                  constant_values=-1).reshape(tp, 1)
    grid = (tp // BT, vp // BV)
    # Per-row vectors travel as (BT, 1) blocks: Mosaic refuses 1-D blocks
    # whose XLA tiling differs from its own. Vocab is the sequential
    # reduction axis of the stats carry; token blocks are independent.
    row = pl.BlockSpec((BT, 1), lambda i, j: (i, 0))
    tile = pl.BlockSpec((BT, BV), lambda i, j: (i, j))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    m, l = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[tile],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((tp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((tp, 1), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(x)

    out = pl.pallas_call(
        _resid_kernel,
        grid=grid,
        in_specs=[tile, row, row, row],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((tp, vp), out_dtype),
        compiler_params=params,
        interpret=interpret,
    )(x, lab, m, l)
    return out[:t, :v]
