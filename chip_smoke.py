#!/usr/bin/env python3
"""Bring-up check of the main paths on one TPU chip, at full width.

    python3 chip_smoke.py               # one chip: device, kernel, serve, train
    python3 chip_smoke.py --four-chips  # four chips: data-parallel training
                                        # against the same batch on one chip

The model is qwen3-0.6b (``configs/qwen3_0_6b.py`` ``CONFIG``) with weights
drawn at random from ``--seed``. The phases run in one process, in order,
and each prints one ``phase <name>: {...}`` line. A phase that fails ends
the script with a non-zero exit. Only when every phase passed does the
last line of standard output read

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

There is no CPU fallback: without a TPU the script exits non-zero before
any phase runs. Timings printed here are notes on a bring-up run, not
measurements. The phase functions take their configuration as arguments,
so the tests run them on the CPU at smoke sizes.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import TrainConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.quant import (dequantize_symmetric,  # noqa: E402
                              quantize_symmetric, symmetric_scales)
from repro.kernels.block_circulant import ops  # noqa: E402
from repro.kernels.block_circulant.ref import (  # noqa: E402
    block_circulant_matmul_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.serve import build_engine, serve_requests  # noqa: E402
from repro.launch.specs import build_model  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.nn.module import init_params  # noqa: E402
from repro.serve.engine import (Request, SamplingParams,  # noqa: E402
                                ServeEngine)
from repro.serve.guard import FINISHED  # noqa: E402

ARCH = "qwen3-0.6b"
# qwen3-0.6b's circulant projections at k=128, as (p, q) block counts:
# q/k/v-fused (32, 8), o (8, 16), gate/up (24, 8), down (8, 24)
QWEN3_SHAPES = ((32, 8), (8, 16), (24, 8), (8, 24))

# Relative error (Frobenius norm of the difference over that of the f32
# dense oracle) allowed per output dtype. bf16 outputs carry their own
# rounding (2^-9) on top of the kernel's f32 accumulation; f32 outputs
# (the weight adjoint) only the kernel's. A wrong index or a lost tile is
# an O(1) error, far above both.
KERNEL_RTOL = {"bfloat16": 2e-2, "float32": 1e-2}
# Prefill logits, relative Frobenius error against the f32 model: the
# registry impl (XLA) on the served weights, computed in f32 with every
# matmul at "highest" precision. The served engines compute in bf16 and
# drift from it by rounding through every layer, by an amount only a chip
# run shows, so the bounds are relative to the XLA engine's drift: that
# must stay within SERVE_XLA_RTOL, and each Pallas engine within
# SERVE_VS_XLA times it plus an allowance per table format (int8 adds the
# per-block quantization step, 1/127 of the block's max). A wrong index or
# a lost tile is an O(1) error.
SERVE_XLA_RTOL = 0.25
SERVE_VS_XLA = 1.5
SERVE_ALLOWANCE = {"off": 5e-3, "int8": 5e-2}
# First-step loss, four chips against one, same global batch: the same
# math reduced in another order.
FOUR_CHIP_LOSS_RTOL = 5e-3


class PhaseError(RuntimeError):
    """A phase ran and its result is wrong."""


def _rel_err(got, want) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def device_phase() -> dict:
    dev = _device()
    if dev["platform"] != "tpu":
        raise PhaseError(f"no TPU: JAX's first device is {dev}")
    return dev


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _check(name, got, want, report, errors):
    err = _rel_err(got, want)
    tol = KERNEL_RTOL[str(jnp.asarray(got).dtype)]
    report[name] = err
    if not err <= tol:
        errors.append(f"{name}: rel err {err:.3g} > {tol:g}")


def kernel_phase(shapes=QWEN3_SHAPES, batches=(8, 2048), k: int = 128, *,
                 seed: int = 0, require_kernel: bool = True) -> dict:
    """``ops.block_circulant_matmul`` against the dense f32 oracle, on bf16
    activations: forward and grad with trainable tables, forward with
    frozen f32 tables, forward with frozen int8 tables. With
    ``require_kernel`` every compiled executable must hold the Pallas
    kernel (``tpu_custom_call``), which interpret mode never does."""
    report, errors = {}, []
    n_exec = compile_s = 0.0

    def compiled(fn, *args):
        nonlocal n_exec, compile_s
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        compile_s += time.perf_counter() - t0
        n_exec += 1
        if require_kernel and _custom_calls(c) == 0:
            raise PhaseError(f"no tpu_custom_call in {fn.__name__}: the "
                             "kernel did not compile for the chip")
        return c

    def fwd(x, w):
        return ops.block_circulant_matmul(x, w)

    def loss(x, w, ct):
        return jnp.sum(ops.block_circulant_matmul(x, w).astype(jnp.float32)
                       * ct)

    def fwd_frozen(x, wr, wi):
        return ops.block_circulant_matmul(x, None, w_freq=(wr, wi), k=k)

    def fwd_int8(x, wr, wi, s):
        return ops.block_circulant_matmul(x, None, w_freq=(wr, wi),
                                          w_scale=s, k=k)

    key = jax.random.PRNGKey(seed)
    for p, q in shapes:
        for B in batches:
            kx, kw, kc, key = jax.random.split(key, 4)
            x = jax.random.normal(kx, (B, q * k), jnp.float32).astype(
                jnp.bfloat16)
            w = jax.random.normal(kw, (p, q, k), jnp.float32) / math.sqrt(
                q * k)
            ct = jax.random.normal(kc, (B, p * k), jnp.float32)
            tag = f"p{p}q{q}B{B}"
            with jax.default_matmul_precision("highest"):
                y_ref = block_circulant_matmul_ref(x.astype(jnp.float32), w)
                dx_ref, dw_ref = jax.grad(
                    lambda x, w: jnp.sum(block_circulant_matmul_ref(x, w)
                                         * ct), argnums=(0, 1))(
                    x.astype(jnp.float32), w)
            _check(f"{tag}/fwd", compiled(fwd, x, w)(x, w), y_ref, report,
                   errors)
            dx, dw = compiled(jax.grad(loss, argnums=(0, 1)), x, w, ct)(
                x, w, ct)
            _check(f"{tag}/dx", dx, dx_ref.astype(jnp.bfloat16), report,
                   errors)
            _check(f"{tag}/dw", dw, dw_ref, report, errors)

            wr, wi = ops.freq_weights(w)
            _check(f"{tag}/frozen_f32",
                   compiled(fwd_frozen, x, wr, wi)(x, wr, wi), y_ref,
                   report, errors)

            s = symmetric_scales(wr, wi)
            qr, qi = quantize_symmetric(wr, s), quantize_symmetric(wi, s)
            # the oracle's weights are the dequantized tables, taken back
            # to the time domain
            w_deq = jnp.fft.irfft(dequantize_symmetric(qr, s)
                                  + 1j * dequantize_symmetric(qi, s),
                                  n=k, axis=-1)
            with jax.default_matmul_precision("highest"):
                y_deq = block_circulant_matmul_ref(x.astype(jnp.float32),
                                                   w_deq)
            _check(f"{tag}/frozen_int8",
                   compiled(fwd_int8, x, qr, qi, s)(x, qr, qi, s), y_deq,
                   report, errors)
    if errors:
        raise PhaseError("; ".join(errors))
    return {"k": k, "executables": int(n_exec),
            "compile_s": round(compile_s, 2),
            "max_rel_err": max(report.values()),
            "worst": max(report, key=report.get)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _prompts(vocab: int, n: int, max_len: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(max(1, max_len // 32), max_len + 1, size=n)
    return [rng.integers(0, vocab, size=int(L)).astype(np.int32)
            for L in lens]


def _prefill_logits(engine, prompts, Sb: int):
    """Last-position logits of ``prompts``, left-padded into one
    (len(prompts), Sb) prefill launch laid out as admission lays it out
    (pads at negative, masked positions). Runs the engine's own prefill
    executable for that bucket, from fresh rows, into slots 0.."""
    Bb = len(prompts)
    toks = np.zeros((Bb, Sb), np.int32)
    pos = np.zeros((Bb, Sb), np.int32)
    for j, p in enumerate(prompts):
        T = p.shape[0]
        toks[j, Sb - T:] = p
        pos[j] = np.arange(Sb, dtype=np.int32) - (Sb - T)
    logits, _, engine.cache = engine._prefill(
        engine.params, jnp.asarray(toks), jnp.asarray(pos), engine.cache,
        jnp.arange(Bb, dtype=jnp.int32))
    return np.asarray(logits, np.float32)


def _f32_logits(cfg, prompts, *, seed: int, Sb: int):
    """Prefill logits of the f32 model: ``cfg``'s impl on the weights the
    served engines hold (drawn from ``seed`` in ``cfg``'s dtypes), taken
    to f32 and computed in f32 with every matmul at "highest" precision.
    Its cache holds just the one prompt bucket: masked slots add nothing."""
    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    model = build_model(cfg32)
    params = jax.tree.map(
        lambda a: (a.astype(jnp.float32)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a),
        init_params(build_model(cfg).specs(), seed))
    with jax.default_matmul_precision("highest"):
        engine = ServeEngine(model, cfg32, params, batch=len(prompts),
                             cache_len=Sb, prompt_buckets=(Sb,))
        logits = _prefill_logits(engine, prompts, Sb)
    del engine
    gc.collect()
    return logits


def serve_phase(cfg, *, seed: int = 0, batch: int = 8, cache_len: int = 2048,
                prompt_buckets=(128, 512), n_requests: int = 8,
                max_new: int = 32, require_kernel: bool = True) -> dict:
    """``cfg`` served three ways through the launcher's engine builder: the
    registry impl (XLA), ``impl="pallas"``, and ``impl="pallas"`` with int8
    tables. Every request must finish with all ``max_new`` tokens, and each
    engine's prefill logits must stay near the f32 model's (see
    ``SERVE_XLA_RTOL``)."""
    prompts = _prompts(cfg.vocab, n_requests, max(prompt_buckets), seed)
    Sb = max(prompt_buckets)
    ref = _f32_logits(cfg, prompts[:batch], seed=seed, Sb=Sb)
    pallas = dataclasses.replace(
        cfg, swm=dataclasses.replace(cfg.swm, impl="pallas"))
    variants = (("xla", cfg, "off"), ("pallas", pallas, "off"),
                ("pallas-int8", pallas, "int8"))
    report, errors = {}, []
    for name, c, quantize in variants:
        engine = build_engine(c, batch=batch, cache_len=cache_len, seed=seed,
                              prompt_buckets=prompt_buckets,
                              quantize=quantize)
        t0 = time.perf_counter()
        n_exec = engine.prewarm()
        compile_s = time.perf_counter() - t0
        reqs = [Request(p, max_new=max_new,
                        sampling=SamplingParams(temperature=0.0, seed=seed))
                for p in prompts]
        outs, statuses = serve_requests(engine, reqs)
        bad = [(i, st, len(o)) for i, (st, o) in enumerate(zip(statuses, outs))
               if st != FINISHED or len(o) != max_new]
        if bad:
            errors.append(f"{name}: requests not FINISHED with {max_new} "
                          f"tokens: {bad}")
        n_kernel = _custom_calls(engine._decode.lower(
            engine.params, jnp.zeros((batch, 1), jnp.int32), engine.cache,
            -jnp.ones((batch,), jnp.int32),
            jnp.arange(batch, dtype=jnp.int32)).compile())
        if require_kernel and c.swm.impl == "pallas" and n_kernel == 0:
            errors.append(f"{name}: no tpu_custom_call in the decode "
                          "executable")
        logits = _prefill_logits(engine, prompts[:batch], Sb)
        err = _rel_err(logits, ref)
        entry = {"prewarm_compile_s": round(compile_s, 2),
                 "executables": n_exec,
                 "tokens": sum(len(o) for o in outs),
                 "statuses": sorted(set(statuses)),
                 "decode_tpu_custom_calls": n_kernel,
                 "logits_finite": bool(np.isfinite(logits).all()),
                 "logits_rel_err_vs_f32": err}
        if not entry["logits_finite"]:
            errors.append(f"{name}: non-finite prefill logits")
        if name == "xla":
            xla_logits, xla_err = logits, err
            bound = SERVE_XLA_RTOL
        else:
            bound = SERVE_VS_XLA * xla_err + SERVE_ALLOWANCE[quantize]
            entry["logits_rel_err_vs_xla"] = _rel_err(logits, xla_logits)
            entry["greedy_first_token_agree"] = float(np.mean(
                logits.argmax(-1) == xla_logits.argmax(-1)))
        entry["logits_bound"] = bound
        if not err <= bound:
            errors.append(f"{name}: logits rel err vs f32 {err:.3g} > "
                          f"{bound:.3g}")
        entry["peak_bytes_in_use"] = _peak_bytes()
        report[name] = entry
        # the engine's jitted methods close a reference cycle over it; free
        # its cache and tables before the next engine allocates its own
        del engine
        gc.collect()
    if errors:
        raise PhaseError("; ".join(errors) + f" | {json.dumps(report)}")
    return report


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _train(cfg, mesh, *, seed: int, steps: int, batch: int, seq: int):
    """``steps`` steps of the launcher's trainer on ``mesh``; returns the
    per-step losses and timings, the restart count, and the state's bytes
    per device as placed against what its shardings call for."""
    with tempfile.TemporaryDirectory() as ckpt:
        tcfg = TrainConfig(seed=seed, total_steps=steps,
                           checkpoint_every=steps + 1, checkpoint_dir=ckpt)
        driver, state = build_trainer(cfg, tcfg, mesh, seq=seq, batch=batch)
        placed, wanted = {}, {}
        for leaf in jax.tree.leaves(state):
            for shard in leaf.addressable_shards:
                d = str(shard.device)
                placed[d] = placed.get(d, 0) + shard.data.nbytes
            n = math.prod(leaf.sharding.shard_shape(leaf.shape))
            for dev in leaf.sharding.device_set:
                wanted[str(dev)] = (wanted.get(str(dev), 0)
                                    + n * leaf.dtype.itemsize)
        with mesh:
            driver.run(state, n_steps=steps, max_restarts=0)
    log = driver.metrics_log
    return {"losses": [m["loss"] for m in log],
            "step_s": [round(m["dt"], 3) for m in log],
            "restarts": driver.restarts,
            "bytes_per_device": placed, "bytes_wanted": wanted}


def _check_train(res: dict, steps: int) -> None:
    losses = res["losses"]
    if len(losses) != steps or not all(math.isfinite(v) for v in losses):
        raise PhaseError(f"losses {losses} over {steps} steps")
    if res["restarts"]:
        raise PhaseError(f"{res['restarts']} restarts")
    if res["bytes_per_device"] != res["bytes_wanted"]:
        raise PhaseError(f"state placed as {res['bytes_per_device']}, "
                         f"shardings call for {res['bytes_wanted']}")


def train_phase(cfg, *, seed: int = 0, steps: int = 3, batch: int = 4,
                seq: int = 512) -> dict:
    """A few train steps on a local mesh of every device, through the
    launcher's trainer: finite loss on every step and no restart."""
    res = _train(cfg, make_local_mesh(), seed=seed, steps=steps,
                 batch=batch, seq=seq)
    _check_train(res, steps)
    res["peak_bytes_in_use"] = _peak_bytes()
    return res


def four_chip_phase(cfg, devices, *, seed: int = 0, steps: int = 2,
                    batch: int = 4, seq: int = 512) -> dict:
    """Data-parallel training on a mesh of ``devices`` against the same
    global batch on the first device alone: first-step losses agree and
    each device holds the state its shardings call for."""
    many = _train(cfg, make_local_mesh(devices), seed=seed, steps=steps,
                  batch=batch, seq=seq)
    one = _train(cfg, make_local_mesh(devices[:1]), seed=seed, steps=steps,
                 batch=batch, seq=seq)
    _check_train(many, steps)
    _check_train(one, steps)
    l_many, l_one = many["losses"][0], one["losses"][0]
    err = abs(l_many - l_one) / abs(l_one)
    out = {"devices": len(devices), "loss_first_step": l_many,
           "loss_first_step_one_device": l_one, "rel_diff": err,
           "bound": FOUR_CHIP_LOSS_RTOL,
           "losses": many["losses"], "losses_one_device": one["losses"],
           "bytes_per_device": many["bytes_per_device"],
           "bytes_one_device": one["bytes_per_device"]}
    if not err <= FOUR_CHIP_LOSS_RTOL:
        raise PhaseError(f"first-step loss {l_many} on {len(devices)} "
                         f"devices vs {l_one} on one: rel diff {err:.3g} > "
                         f"{FOUR_CHIP_LOSS_RTOL:g}")
    return out


# ---------------------------------------------------------------------------


def _run(name, fn, *args, **kw):
    try:
        out = fn(*args, **kw)
    except Exception as e:  # report the phase, then fail the script
        traceback.print_exc()
        print(f"phase {name}: FAILED: {e}", flush=True)
        sys.exit(1)
    print(f"phase {name}: {json.dumps(out)}", flush=True)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)

    dev = _run("device", device_phase)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    if args.four_chips:
        if dev["count"] != 4:
            print(f"phase four-chips: FAILED: needs 4 devices, {dev}")
            sys.exit(1)
        _run("four-chips", four_chip_phase, cfg, jax.devices(),
             seed=args.seed)
    else:
        _run("kernel", kernel_phase, seed=args.seed)
        _run("serve", serve_phase, cfg, seed=args.seed)
        _run("train", train_phase, cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
