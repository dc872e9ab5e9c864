"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles each kernel
for a ``v5e:2x2`` topology that is described, not attached, and raises what
the chip's compiler would raise (Mosaic layout refusals, VMEM overflows).
Interpret-mode tests cannot see those. The topology is described inside a
module-scoped fixture, never at import, so that every test worker collects
the same tests and only the worker that runs this file loads the TPU library.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.residual_xent import residual_xent_kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("vocab", [100352, 151936])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_residual_xent_compiles_for_v5e(vocab, dtype, one_chip):
    logits = jax.ShapeDtypeStruct((4096, vocab), dtype, sharding=one_chip)
    labels = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    compiled = residual_xent_kernel.lower(
        logits, labels, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    # stablelm-1.6b: 32 heads of 64, no GQA, at a 2048-token context
    q = jax.ShapeDtypeStruct((1, 2048, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = flash_attention_kernel.lower(
        q, q, q, causal=True, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
