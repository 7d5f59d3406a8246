"""Pallas kernel sweeps (interpret mode) vs the pure-jnp dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_circulant import block_circulant_matmul
from repro.kernels.block_circulant.ref import block_circulant_matmul_ref

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
           dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,p,q,k", [
    (4, 3, 5, 8), (16, 2, 2, 128), (7, 1, 3, 64), (32, 4, 4, 16),
    (3, 2, 2, 2), (1, 1, 1, 256), (130, 2, 3, 32),   # odd batch > block
])
def test_kernel_shape_dtype_sweep(B, p, q, k, dtype):
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (p, q, k), jnp.float32).astype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, q * k),
                          jnp.float32).astype(dtype)
    y = block_circulant_matmul(x, w)
    y_ref = block_circulant_matmul_ref(
        x.astype(jnp.float32), w.astype(jnp.float32))
    assert y.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **_tol(dtype)
    )


def test_kernel_3d_batch():
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 48))
    y = block_circulant_matmul(x, w)
    assert y.shape == (2, 5, 32)
    y_ref = block_circulant_matmul_ref(x.reshape(10, 48), w).reshape(2, 5, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4,
                               atol=2e-4)


def test_kernel_custom_vjp_matches_autodiff_of_ref():
    B, p, q, k = 4, 2, 3, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (p, q, k))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, q * k))
    t = jax.random.normal(jax.random.PRNGKey(2), (B, p * k))
    f_k = lambda x, w: (block_circulant_matmul(x, w) * t).sum()
    f_r = lambda x, w: (block_circulant_matmul_ref(x, w) * t).sum()
    gx_k, gw_k = jax.grad(f_k, (0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_r, (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw_k), np.asarray(gw_r),
                               rtol=1e-4, atol=1e-4)


def test_kernel_inside_jit_and_grad_pipeline():
    """Kernel must compose with jit + optimizer-style updates."""
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
    y = jax.random.normal(jax.random.PRNGKey(2), (8, 32))

    @jax.jit
    def loss(w):
        return ((block_circulant_matmul(x, w) - y) ** 2).mean()

    l0 = loss(w)
    for _ in range(20):
        w = w - 0.1 * jax.grad(loss)(w)
    assert float(loss(w)) < float(l0)


def test_interpret_mode_only_on_cpu_backend(monkeypatch):
    from repro.kernels.block_circulant import ops
    assert ops.resolve_interpret() is True            # CPU backend here
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret() is False
    assert ops.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True"):
        ops.resolve_interpret(True)
