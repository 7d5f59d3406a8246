"""CPU tests of a whole serving run at a tiny size, the chip check skipped.

The reference is tied to the program on the smoke configurations: with
float32 weights and compute, every served token is the reference's first
choice. Then the timed path is broken underneath, once per fault a serving
cell can have, and ``correct`` has to come out false.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import run

BENCH = Path(__file__).resolve().parent
SEED = 2**33 + 17

# the repository's smoke sizes (configs/*.py SMOKE), float32 throughout
SMOKE = {
    "qwen3-0.6b": dict(num_hidden_layers=3, hidden_size=64,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, intermediate_size=128, vocab_size=256,
                       swm_block_size=8),
    # d_ff 172 is not a multiple of 8: the FFN tables fall to k = 4
    "deepseek-7b": dict(num_hidden_layers=3, hidden_size=64,
                        num_attention_heads=4, num_key_value_heads=4,
                        head_dim=16, intermediate_size=172, vocab_size=256,
                        swm_block_size=8),
}
TINY_MIX = {
    "rate_per_s": 20.0, "batch": 2, "cache_len": 64, "prompt_buckets": [8, 16],
    "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 16},
    "output_len": {"dist": "uniform", "min": 2, "max": 8},
    "check": {"sample": 4, "ref_batch": 2},
}
LIMITS = {"mean_gap": 1e-6, "min_checked_tokens": 4}


def tiny_cell(config="qwen3-0.6b", traffic="chat", **cfg):
    """A configuration file and a mix, both cut to smoke size."""
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    cj = dict(conf, **dict(SMOKE[config], **cfg), param_dtype="float32",
              compute_dtype="float32", max_position_embeddings=64)
    return {"name": f"{config}.{traffic}", "chips": 1, "config": cj,
            "traffic": dict(mix, **TINY_MIX), "limits": LIMITS}


def wide_cell():
    """A wider vocabulary, longer answers and full slots: the near-ties
    that a fault or int8 rounding flips, and decode launches of two rows."""
    c = tiny_cell(vocab_size=16384, hidden_size=128, intermediate_size=256)
    c["traffic"]["output_len"] = {"dist": "uniform", "min": 16, "max": 32}
    c["traffic"]["rate_per_s"] = 200.0      # both slots busy
    c["traffic"]["check"] = {"sample": 16, "ref_batch": 4}
    return c


def run_tiny(cell, seconds=1.5, control=""):
    drv = run.load_module(BENCH / "entries" / "serve.py")
    return drv.run(cell, seed=SEED, seconds=seconds, trace=False,
                   t_process=time.perf_counter(), control=control)


@pytest.mark.parametrize("config,traffic", [("qwen3-0.6b", "chat"),
                                            ("deepseek-7b", "offline")])
def test_reference_agrees_with_the_served_tokens(config, traffic):
    res = run_tiny(tiny_cell(config, traffic))
    chk = res["check"]
    assert res["correct"], chk
    assert chk["checked_tokens"]["value"] >= 4
    assert chk["mean_gap"]["value"] <= 1e-6
    assert res["readings"]["served"]["widest"] <= 1e-4
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["counters"]["compiles_in_window"] == 0


def _alter_tokens(monkeypatch):
    import repro.serve.engine as eng

    orig, n = eng._sample_token, [0]

    def altered(logits, sp, rng):
        n[0] += 1
        tok = orig(logits, sp, rng)
        return (tok + 1) % logits.shape[-1] if n[0] % 3 == 0 else tok

    monkeypatch.setattr(eng, "_sample_token", altered)


def _state_unchanged(monkeypatch):
    from repro.serve.runner import DecoderRunner

    orig = DecoderRunner.decode

    def stale(self, params, tokens, state, pos, slot_idx):
        logits, ok, _ = orig(self, params, tokens, state, pos, slot_idx)
        return logits, ok, state

    monkeypatch.setattr(DecoderRunner, "decode", stale)


def _half_batch(monkeypatch):
    from repro.serve.runner import DecoderRunner

    orig = DecoderRunner.decode

    def half(self, params, tokens, state, pos, slot_idx):
        logits, ok, st = orig(self, params, tokens, state, pos, slot_idx)
        h = (logits.shape[0] + 1) // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), \
            ok, st

    monkeypatch.setattr(DecoderRunner, "decode", half)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(wide_cell())
    assert not res["correct"], res["check"]
    assert res["check"]["mean_gap"]["value"] > LIMITS["mean_gap"]


def test_int8_control_reads_above_the_program():
    """The control goes through the harness's own judgement and fails it,
    while the program, on the same requests, passes."""
    res = run_tiny(wide_cell(), control="int8")
    ctl, served = res["readings"]["control"], res["readings"]["served"]
    assert res["correct"], res["check"]
    assert res["control_correct"] is False, res["control_check"]
    assert res["control_check"]["mean_gap"]["value"] == ctl["mean"]
    assert ctl["mean"] > LIMITS["mean_gap"]
    assert ctl["widest"] > 10 * served["widest"]
    assert ctl["mean"] > 10 * served["mean"]


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.chat",
           "--seed", str(SEED), "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=BENCH.parent, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a checkout with only the benchmark's own files prints no result
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_gap_reading_on_a_known_sequence():
    """The widest-gap reading picks the served token's position."""
    import reference

    cell = tiny_cell()
    cj = cell["config"]
    drv = run.load_module(BENCH / "entries" / "serve.py")
    from repro.launch.specs import build_model

    from weights import make_weights

    w = make_weights(build_model(drv.model_config(cj)).specs(), SEED)
    prompt = np.arange(1, 9, dtype=np.int32)
    seq = prompt
    head = np.asarray(reference._head(cj, w))
    hid = reference._jit_hidden(reference.cfg_key(cj), "f32")
    for _ in range(4):           # the reference's own greedy continuation
        h = np.asarray(hid(w, jnp.asarray(seq[None])))
        seq = np.append(seq, np.int32(np.argmax(h[0, -1] @ head.T)))
    served, _ = reference.served_gaps(cj, w, [seq], [len(prompt)],
                                      pad_to=32, batch=2)
    assert served[0].shape == (4,)
    assert served[0].max() == pytest.approx(0.0, abs=1e-5)
    bad = seq.copy()
    bad[-1] = (bad[-1] + 1) % cj["vocab_size"]
    served, _ = reference.served_gaps(cj, w, [bad], [len(prompt)],
                                      pad_to=32, batch=2)
    assert served[0][-1] > 1e-3 and served[0][:-1].max() < 1e-5
