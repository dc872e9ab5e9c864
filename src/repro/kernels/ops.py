"""Public jit'd entry points for the Pallas kernels.

The backend decides how a kernel runs: on TPU it always lowers through
Mosaic; on any other backend (the CPU test container) the kernel body is
emulated in interpret mode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.residual_xent import residual_xent_kernel
from repro.kernels import ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def residual_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                  use_kernel: bool = True) -> jnp.ndarray:
    """Pseudo-residual r = onehot(labels) - softmax(logits).

    logits: (..., V); labels: (...,) int32. Returns f32 residual.
    """
    lead = logits.shape[:-1]
    v = logits.shape[-1]
    flat = logits.reshape(-1, v)
    lab = labels.reshape(-1)
    if use_kernel:
        out = residual_xent_kernel(flat, lab, interpret=_interpret())
    else:
        out = ref.residual_xent_ref(flat, lab)
    return out.reshape(*lead, v)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    use_kernel: bool = True) -> jnp.ndarray:
    """GQA flash attention. q: (B,S,H,hd); k,v: (B,S,KV,hd) -> (B,S,H,hd)."""
    if use_kernel:
        return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                      interpret=_interpret())
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
