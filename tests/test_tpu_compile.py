"""The block-circulant kernels compile for a TPU v5e, without one attached.

The TPU compiler is asked for each kernel of the main path at qwen3-0.6b's
projection shapes (k=128) and at k=64, for a described ``v5e:2x2`` chip.
The default backend stays the CPU, so every call passes
``interpret=False``: left to choose, the ops would pick the interpreter,
whose executables hold no kernel. A compiled executable holds the kernel
exactly when its text has a ``tpu_custom_call``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_circulant import ops

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile written to the persistent cache here cannot be read back
    # without a chip; keep it off for this module.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _fwd(x, w):
    return ops.block_circulant_matmul(x, w, interpret=False)


def _grad(x, w):
    def loss(x, w):
        return ops.block_circulant_matmul(x, w, interpret=False).astype(
            jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1))(x, w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_compiles(one_chip, dtype):
    p, q, k, B = 32, 8, 128, 256
    text = _compile(_fwd, one_chip, ((B, q * k), dtype),
                    ((p, q, k), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("p,q,k", [(8, 24, 128), (16, 48, 64)])
def test_grad_compiles(one_chip, p, q, k):
    """The dx and dw kernels (dw: ``bc_dw_pallas``; the forward launch
    is dead code under this loss). At k=64 the blocks stream on their own
    axis."""
    text = _compile(_grad, one_chip, ((256, q * k), jnp.bfloat16),
                    ((p, q, k), jnp.float32))
    assert text.count("tpu_custom_call") >= 2


def test_frozen_int8_compiles_at_q24(one_chip):
    """The per-block scale tile is legal for q not a multiple of 128 (a
    (pt, qt) block of a (p, q) scale array was refused at q=24)."""
    p, q, k = 8, 24, 128
    K = k // 2 + 1

    def fwd(x, wr, wi, s):
        return ops.block_circulant_matmul(x, None, w_freq=(wr, wi),
                                          w_scale=s, k=k, interpret=False)

    text = _compile(fwd, one_chip, ((256, q * k), jnp.bfloat16),
                    ((p, q, K), jnp.int8), ((p, q, K), jnp.int8),
                    ((p, q), jnp.float32))
    assert "tpu_custom_call" in text


def test_forward_compiles_at_k64(one_chip):
    p, q, k = 16, 48, 64
    text = _compile(_fwd, one_chip, ((256, q * k), jnp.bfloat16),
                    ((p, q, k), jnp.float32))
    assert "tpu_custom_call" in text
