"""The block-circulant kernels compile for a TPU v5e, without one attached.

The TPU compiler is asked for each kernel of the main path at qwen3-0.6b's
projection shapes (k=128) and at k=64, and for the serving decode step's
memory, for a described ``v5e:2x2`` chip.
The default backend stays the CPU, so every call passes
``interpret=False``: left to choose, the ops would pick the interpreter,
whose executables hold no kernel. A compiled executable holds the kernel
exactly when its text has a ``tpu_custom_call``.
"""

import dataclasses
import json
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import SWMConfig
from repro.configs.registry import get_config
from repro.kernels.block_circulant import ops
from repro.kernels.block_circulant.plan import freeze_params
from repro.launch.specs import build_model
from repro.nn.module import init_params
from repro.serve.runner import DecoderRunner

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


def test_decode_in_place_keeps_no_pool_copy(one_chip):
    """The serving decode step at qwen3-0.6b's widths (4 layers, a small
    vocab, 8 slots x 2048) decodes in place on the donated slot pool: its
    temporaries stay under 5% of the pool. Gathering the launched rows,
    decoding them and scattering them back held three pool copies."""
    cj = json.loads((pathlib.Path(__file__).parents[1] / "bench" / "configs"
                     / "qwen3-0.6b.json").read_text())
    cfg = dataclasses.replace(
        get_config(cj["registry"]), n_layers=4, vocab=512,
        d_model=cj["hidden_size"], n_heads=cj["num_attention_heads"],
        n_kv_heads=cj["num_key_value_heads"], head_dim=cj["head_dim"],
        d_ff=cj["intermediate_size"], qk_norm=cj["qk_norm"],
        param_dtype=cj["param_dtype"], compute_dtype=cj["compute_dtype"],
        swm=SWMConfig(block_size=cj["swm_block_size"], impl=cj["swm_impl"]))
    slots, cache_len = 8, 2048
    runner = DecoderRunner(build_model(cfg), cfg, cache_len)
    specs = runner.specs()
    params = jax.eval_shape(
        lambda: freeze_params(specs, init_params(specs, 0)))
    pool = jax.eval_shape(lambda: runner.init_state(slots))

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    mem = jax.jit(runner.decode, donate_argnums=(2,)).lower(
        placed(params), tokens, placed(pool), rows, rows,
    ).compile().memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 0.05 * pool_bytes, (
        mem.temp_size_in_bytes, pool_bytes)


def test_mla_serving_temporaries(one_chip):
    """DeepSeek-V2-Lite at its published widths and depth (a small vocab),
    32 slots x 4096: decode keeps its temporaries under 5% of the donated
    latent pool (a layout that copied the pool per write, or a read that
    fetched a layer's rows whole, would not). The prefill launches the
    engine makes hold at most ``max_prefill_tokens`` = 9,518 tokens: the
    longest bucket's, 8 x 1024, keeps its temporaries under 6 GB (a
    dispatch that ran every expert over the launch's tokens would need
    (64, 8192, 2048) buffers and its expert hiddens), and the one of the
    most rows, 32 x 256 (a copy of the whole pool), fits one 16 GB v5e
    beside the pool and the weights with 2 GB to spare."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), vocab=512)
    slots, cache_len = 32, 4096
    runner = DecoderRunner(build_model(cfg), cfg, cache_len)
    specs = runner.specs()
    params = jax.eval_shape(
        lambda: freeze_params(specs, init_params(specs, 0)))
    pool = jax.eval_shape(lambda: runner.init_state(slots))

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    pool_bytes = nbytes(pool)
    mem = jax.jit(runner.decode, donate_argnums=(2,)).lower(
        placed(params), sds((slots, 1)), placed(pool), sds((slots,)),
        sds((slots,))).compile().memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 0.05 * pool_bytes, (
        mem.temp_size_in_bytes, pool_bytes)
    assert runner.max_prefill_tokens == 9518
    for (B, S), limit in (((8, 1024), 6e9),
                          ((32, 256), 14e9 - pool_bytes - nbytes(params))):
        assert B * S <= runner.max_prefill_tokens
        mem = jax.jit(runner.prefill, donate_argnums=(3,)).lower(
            placed(params), sds((B, S)), sds((B, S)), placed(pool),
            sds((B,))).compile().memory_analysis()
        assert mem.alias_size_in_bytes == pool_bytes
        assert mem.temp_size_in_bytes < limit, (B, S, mem.temp_size_in_bytes)


def test_mla_decode_reads_the_pool_in_the_kernel(one_chip):
    """DeepSeek-V2-Lite's decode at 32 slots x 4096 (published widths and
    depth, a small vocab): both layer groups' latent attention is the
    Mosaic kernel, under the ``attention`` scope, and nothing slices rows
    of the latent pool (640 wide a position) out for XLA to read. The
    chunk loop it replaced sliced 32 x 512 positions of a layer per step
    and gathered the launched rows from them."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), vocab=512)
    slots, cache_len = 32, 4096
    runner = DecoderRunner(build_model(cfg), cfg, cache_len)
    specs = runner.specs()
    params = jax.eval_shape(
        lambda: freeze_params(specs, init_params(specs, 0)))
    pool = jax.eval_shape(lambda: runner.init_state(slots))

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    text = jax.jit(runner.decode, donate_argnums=(2,)).lower(
        placed(params), sds((slots, 1)), placed(pool), sds((slots,)),
        sds((slots,))).compile().as_text()
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and "latent_decode_attention" in ln]
    assert len(kernels) == 2, len(kernels)
    assert all("/attention/" in ln for ln in kernels)
    pool_slices = re.findall(
        r"= bf16\[([\d,]*,640)\]\S* dynamic-slice\(", text)
    assert not pool_slices, pool_slices
