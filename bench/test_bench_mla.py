"""CPU tests of the latent-attention MoE cell's own files: its entry
(``entries/serve_mla.py``) end to end at a tiny size, its reference
(``reference_mla.py``), its FLOP counts (``flops_mla.py``) and the readers
``moe_ms``, ``step_mfu.rsn`` and ``prefill_share``."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import flops_mla
import run
import trace_reduce as tr
import trace_scopes as ts
from clientstats import Record

BENCH = Path(__file__).resolve().parent
CELL = "deepseek-v2-lite.reasoning"
SEED = 2**33 + 29
# the repository's smoke sizes (configs/deepseek_v2_lite.py SMOKE), float32
SMOKE = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=172, vocab_size=256,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, n_routed_experts=8, num_experts_per_tok=3,
             moe_intermediate_size=32, swm_block_size=8)
# answers of 1500-2000 tokens: as in the cell, none finishes in the window
TINY_MIX = {"clients": 4, "pool": 16, "batch": 2, "cache_len": 2048,
            "prompt_buckets": [16],
            "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 2, "max": 16},
            "output_len": {"dist": "uniform", "min": 1500, "max": 2000},
            "check": {"sample": 4, "ref_batch": 2}}
LIMITS = {"mean_gap": 1e-6, "min_checked_tokens": 4}


def _entry():
    return run.load_module(BENCH / "entries" / "serve_mla.py")


def tiny_cell():
    cell = run.load_cell(CELL)
    cj = dict(cell["config"], **SMOKE, param_dtype="float32",
              compute_dtype="float32", max_position_embeddings=2048)
    return dict(cell, config=cj, traffic=dict(cell["traffic"], **TINY_MIX),
                limits=LIMITS)


def test_served_tokens_agree_and_the_int8_control_does_not():
    """No request finishes in the window, and the check reads the tokens
    the running ones were served by the close: at float32 every one is the
    reference's first choice; the int8 control, judged by the same rule,
    is not correct."""
    res = _entry().run(tiny_cell(), seed=SEED, seconds=1.0, trace=False,
                       t_process=time.perf_counter(), control="int8")
    assert all(r.status is None for r in res["records"])
    assert res["check"]["checked_tokens"]["value"] >= 100
    assert res["correct"], res["check"]
    assert res["check"]["mean_gap"]["value"] <= 1e-6
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["counters"]["compiles_in_window"] == 0
    share = run.load_module(BENCH / "metrics" / "prefill_share.py")
    assert 0 < share.read(res, "prefill_share.rsn") <= 100
    assert res["control_correct"] is False
    assert res["readings"]["control"]["mean"] > LIMITS["mean_gap"]


def test_reference_reads_its_own_continuation_and_a_flipped_token():
    import jax.numpy as jnp

    import reference
    import reference_mla
    from weights import make_weights

    from repro.launch.specs import build_model

    cj = tiny_cell()["config"]
    w = make_weights(build_model(_entry().model_config(cj)).specs(), SEED)
    head = np.asarray(reference._head(cj, w))
    hid = reference_mla._jit_hidden(json.dumps(cj, sort_keys=True), "f32")
    seq = np.arange(1, 9, dtype=np.int32)
    for _ in range(4):           # the reference's own greedy continuation
        h = np.asarray(hid(w, jnp.asarray(seq[None])))
        seq = np.append(seq, np.int32(np.argmax(h[0, -1] @ head.T)))
    served, _ = reference_mla.served_gaps(cj, w, [seq], [8], pad_to=32,
                                          batch=1)
    assert served[0].shape == (4,)
    assert served[0].max() == pytest.approx(0.0, abs=1e-5)
    bad = seq.copy()
    bad[-1] = (bad[-1] + 1) % cj["vocab_size"]
    served, _ = reference_mla.served_gaps(cj, w, [bad], [8], pad_to=32,
                                          batch=1)
    assert served[0][-1] > 1e-3 and served[0][:-1].max() < 1e-5


def test_flops_at_the_published_shapes():
    """Decode is absorbed: per attended position 2 H (r + dr) + 2 H r =
    34,816 FLOPs against the expanded prefill's 2 H (dn + dr + dv) =
    10,240; the MoE layers count 6 routed and the shared experts, not 64."""
    cj = run.load_cell(CELL)["config"]
    assert flops_mla.attn_flops_per_pos(cj, True) == 34_816
    assert flops_mla.attn_flops_per_pos(cj, False) == 10_240
    dense = flops_mla.token_body_flops(cj, 0, True)
    moe = flops_mla.token_body_flops(cj, 1, True)
    assert 0 < moe < dense    # 8 x 1408 wide (6 + 2 shared) < 10944
    d1 = flops_mla.decode_flops(cj, 1)
    assert flops_mla.decode_flops(cj, 101) - d1 == 27 * 100 * 34_816
    assert flops_mla.prefill_flops(cj, 1) < d1 + 27 * 34_816


def _trace(moe_s, launches=4):
    return {"busy_s": 0.5, "window_s": 1.0, "devices": 1, "moe_s": moe_s,
            "programs": {"decode": {"launches": launches, "device_s": 0.4}}}


def test_readers_on_a_fixture_and_without_a_trace():
    moe_ms = run.load_module(BENCH / "metrics" / "moe_ms.py")
    mfu = run.load_module(BENCH / "metrics" / "step_mfu.rsn.py")
    cj = run.load_cell(CELL)["config"]
    recs = [Record(due=0.0, prompt_len=200, max_new=4,
                   tok_times=[0.1, 0.2, 0.3, 0.4])]
    res = {"trace": _trace(0.02), "trace_t": (0.0, 1.0), "records": recs,
           "config": cj, "device": {"kind": "TPU v5 lite"}}
    assert moe_ms.read(res, "moe_ms.rsn") == pytest.approx(5.0)
    want = (flops_mla.prefill_flops(cj, 200)
            + sum(flops_mla.decode_flops(cj, 200 + i) for i in (1, 2, 3)))
    assert mfu.read(res, "step_mfu.rsn") == pytest.approx(
        100 * want / (0.5 * 197e12))
    for r in ({"trace": None, "trace_t": (None, None), "records": recs,
               "config": cj}, dict(res, trace=_trace(None))):
        assert moe_ms.read(r, "moe_ms.rsn") is None
    assert mfu.read({"trace": None, "trace_t": (None, None), "records": recs,
                     "config": cj}, "step_mfu.rsn") is None
    share = run.load_module(BENCH / "metrics" / "prefill_share.py")
    assert share.read({"prefill_step_s": 2.5, "t0": 10.0, "t1": 60.0},
                      "prefill_share.rsn") == pytest.approx(5.0)
    assert share.read({"t0": 10.0, "t1": 60.0}, "prefill_share.rsn") is None


def test_moe_seconds_counts_ops_under_moe_at_any_depth():
    """Self time of the decode module's ops whose path holds ``moe`` (its
    circulant projections included), none outside the decode module."""
    dev = "/device:TPU:0"
    ev = [tr.Ev("/host:CPU", "python3", tr.WINDOW_SPAN, 0.0, 10.0),
          tr.Ev(dev, tr.MODULES_LINE, "jit_decode(1)", 1.0, 5.0)]
    ops = [ts.Op(dev, tr.MODULES_LINE, "jit_decode(1)", 1.0, 5.0, ""),
           ts.Op(dev, tr.OPS_LINE, "%while", 1.0, 4.0, "jit(decode)/while"),
           ts.Op(dev, tr.OPS_LINE, "%f1", 1.0, 2.0,
                 "jit(decode)/while/body/checkpoint(moe)/top_k"),
           ts.Op(dev, tr.OPS_LINE, "%f2", 2.0, 2.5,
                 "jit(decode)/while/body/moe/circulant/fft"),
           ts.Op(dev, tr.OPS_LINE, "%f3", 2.5, 3.0,
                 "jit(decode)/while/body/attention/dot_general"),
           ts.Op(dev, tr.OPS_LINE, "%f4", 6.0, 7.0, "jit(prefill)/moe/x")]
    moe_s = _entry().moe_seconds(ev, ops, r"^jit_decode\b")
    assert moe_s == pytest.approx(1.5)
    assert _entry().moe_seconds(ev[1:], ops, r"^jit_decode\b") is None


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(SEED), "--seconds", "1", "--trace", "1"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
