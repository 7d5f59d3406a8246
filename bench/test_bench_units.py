"""CPU tests of the benchmark's arithmetic: load generation, client-side
statistics, required FLOPs and the trace reduction on synthetic events."""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np
import pytest

import clientstats as cs
import flops
import loadgen
import trace_reduce as tr

BENCH = Path(__file__).resolve().parent
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


# -- load generation ---------------------------------------------------------

@pytest.mark.parametrize("mix", MIXES)
def test_generator_deterministic_from_seed(mix):
    m = _mix(mix)
    a = loadgen.make_requests(m, 2**33 + 7, 10, 1000)
    b = loadgen.make_requests(m, 2**33 + 7, 10, 1000)
    c = loadgen.make_requests(m, 2**33 + 8, 10, 1000)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    m = _mix(mix)
    a = loadgen.make_requests(m, 1, 20, 1000)
    b = loadgen.make_requests(m, 99, 20, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert a[-1].due_s == pytest.approx(b[-1].due_s)
    for r in a:
        assert m["prompt_len"]["min"] <= len(r.prompt) <= m["prompt_len"]["max"]
        assert len(r.prompt) + r.max_new - 1 <= m["cache_len"]


def test_open_loop_rate_and_quantiles():
    m = {"loop": "open", "rate_per_s": 4.0,
         "prompt_len": {"dist": "lognormal", "median": 100, "sigma": 0.5,
                        "min": 1, "max": 10_000},
         "output_len": {"dist": "uniform", "min": 8, "max": 32}}
    reqs = loadgen.make_requests(m, 5, 100, 50)
    due = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0
    assert len(reqs) / due[-1] == pytest.approx(4.0, rel=0.05)
    assert np.median([len(r.prompt) for r in reqs]) == pytest.approx(100,
                                                                     rel=0.03)
    outs = [r.max_new for r in reqs]
    assert min(outs) == 8 and max(outs) == 32


# -- client-side statistics --------------------------------------------------

def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert cs.percentile(v, 99) == 99
    assert cs.percentile(v, 95) == 95
    assert cs.percentile([3.0], 95) == 3.0
    assert cs.percentile([], 50) is None


def test_ttft_counts_every_request_due_in_the_window():
    recs = [cs.Record(due=0.5, prompt_len=1, max_new=1, tok_times=[0.7]),
            cs.Record(due=1.0, prompt_len=1, max_new=1, tok_times=[]),
            cs.Record(due=9.0, prompt_len=1, max_new=1, tok_times=[12.0]),
            cs.Record(due=-1.0, prompt_len=1, max_new=1, tok_times=[0.1]),
            cs.Record(due=10.0, prompt_len=1, max_new=1, tok_times=[])]
    got = sorted(cs.ttfts(recs, 0.0, 10.0))
    # the unanswered one counts at its age at the close, the late one too
    assert got == pytest.approx([0.2, 1.0, 9.0])


def test_inter_token_gaps_and_rate_use_the_whole_window():
    recs = [cs.Record(due=0, prompt_len=1, max_new=5,
                      tok_times=[0.5, 1.0, 3.0, 11.0]),
            cs.Record(due=0, prompt_len=1, max_new=2, tok_times=[-0.5, 2.0])]
    assert sorted(cs.inter_token_gaps(recs, 0.0, 10.0)) == [0.5, 2.0]
    assert cs.tokens_in_window(recs, 0.0, 10.0) == 4


# -- required FLOPs ----------------------------------------------------------

CFG = {"hidden_size": 1024, "intermediate_size": 3072,
       "num_attention_heads": 16, "num_key_value_heads": 8, "head_dim": 128,
       "num_hidden_layers": 28, "vocab_size": 151936, "swm_block_size": 128}


def test_circulant_count_is_the_frequency_domain_count():
    # (p, q, k) = (8, 24, 128): q forward + p inverse rFFTs, 8pq(k/2+1)
    fft = 5 * 128 * 7
    assert flops.circulant_flops(1024, 3072, 128) == (
        24 * fft + 8 * 8 * 24 * 65 + 8 * fft)
    assert flops.proj_flops(dict(CFG, swm_block_size=1), 1024, 3072) == (
        2 * 1024 * 3072)
    # a block that does not divide falls to the largest that does
    assert flops.block_size(128, 4096, 11008) == 128
    assert flops.block_size(128, 64, 172) == 4


def test_counts_match_the_program_freq_count_and_ignore_impl():
    from repro.core.circulant import swm_flops

    for m, n in ((2048, 1024), (1024, 1024), (1024, 2048), (3072, 1024)):
        assert flops.circulant_flops(m, n, 128) == swm_flops(1, m, n, 128,
                                                             impl="freq")
    base = flops.decode_flops(CFG, 100)
    for impl in ("paper", "freq", "dft", "pallas"):
        assert flops.decode_flops(dict(CFG, swm_impl=impl), 100) == base
        assert flops.prefill_flops(dict(CFG, swm_impl=impl), 64) == (
            flops.prefill_flops(CFG, 64))


def test_token_counts_at_known_shapes():
    L, attn = 28, 4 * 16 * 128
    body = flops.token_body_flops(CFG)
    assert flops.head_flops(CFG) == 2 * 151936 * 1024
    assert flops.decode_flops(CFG, 10) == L * (body + attn * 10) + (
        2 * 151936 * 1024)
    # prefill: causal attention over 1..n, logits once
    assert flops.prefill_flops(CFG, 3) == L * (3 * body + attn * 6) + (
        2 * 151936 * 1024)
    assert flops.train_flops(CFG, 3) == 3 * (
        L * (3 * body + attn * 6) + 3 * 2 * 151936 * 1024)
    # the circulant body needs far less than dense
    dense = flops.token_body_flops(dict(CFG, swm_block_size=1))
    assert 10 < dense / body < 40


# -- trace reduction ---------------------------------------------------------

DEV = "/device:TPU:0"


def _ev(plane, line, name, a, b):
    return tr.Ev(plane, line, name, a, b)


def _synthetic():
    return [
        _ev("/host:CPU", "python3", "bench.window", 1.0, 2.0),
        _ev("/host:CPU", "python3", "bench.step", 1.0, 1.5),
        _ev("/host:CPU", "python3", "bench.wait", 1.5, 2.0),
        _ev(DEV, "XLA Modules", "jit_decode(7)", 0.9, 1.2),
        _ev(DEV, "XLA Modules", "jit_prefill(3)", 1.3, 1.4),
        _ev(DEV, "XLA Modules", "jit_decode(7)", 1.8, 1.9),
        _ev(DEV, "XLA Ops", "fusion.1", 0.9, 1.1),
        _ev(DEV, "XLA Ops", "fusion.2", 1.05, 1.2),      # overlaps fusion.1
        _ev(DEV, "XLA Ops", "dot.3", 1.3, 1.4),
        _ev(DEV, "XLA Ops", "fusion.1", 1.8, 1.9),
    ]


def test_reduce_busy_programs_gaps():
    r = tr.reduce_events(_synthetic(), {"decode": r"^jit_decode\b",
                                        "prefill": r"^jit_prefill\b"})
    assert r["window_s"] == pytest.approx(1.0)
    # busy: [1.0, 1.2] + [1.3, 1.4] + [1.8, 1.9], clipped to the window
    assert r["busy_s"] == pytest.approx(0.4)
    assert r["programs"]["decode"]["device_s"] == pytest.approx(0.3)
    assert r["programs"]["decode"]["launches"] == 2
    assert r["programs"]["prefill"]["device_s"] == pytest.approx(0.1)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.2)
    assert ops["fusion.2"] == pytest.approx(0.15)
    # gaps: [1.2,1.3] step, [1.4,1.8] step/wait at 1.6 -> wait, [1.9,2.0]
    names = [g[0] for g in r["idle_gaps"]]
    secs = [g[1] for g in r["idle_gaps"]]
    assert secs == pytest.approx([0.4, 0.1, 0.1])
    assert names[0] == "bench.wait"
    assert set(names[1:]) == {"bench.step", "bench.wait"}
    assert r["host_spans"]["bench.step"] == {"count": 1, "total_s": 0.5}


def test_reduce_needs_window_and_device():
    ev = _synthetic()
    assert tr.reduce_events([e for e in ev if e.name != tr.WINDOW_SPAN],
                            {}) is None
    assert tr.reduce_events([e for e in ev if e.plane != DEV], {}) is None


def test_merge():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    assert math.isclose(sum(b - a for a, b in tr.merge([(0, 1), (0, 1)])),
                        1.0)


def test_reduce_a_recorded_tpu_trace():
    """A trace recorded on a TPU v5e (``testdata/tpu_probe.xplane.pb``):
    inside a ``bench.window`` span, three rounds of ``bench.step`` (one
    launch each of two jitted programs, ``prefill`` and ``decode``, on
    bf16[512, 512]) and ``bench.wait`` (a 2 ms sleep)."""
    ev = tr.load(str(BENCH / "testdata" / "tpu_probe.xplane.pb"))
    r = tr.reduce_events(ev, {"decode": r"^jit_decode\b",
                              "prefill": r"^jit_prefill\b"})
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.012958433, rel=1e-6)
    # the programs' first launches fell before the window opened
    assert r["programs"]["decode"]["launches"] == 2
    assert r["programs"]["prefill"]["launches"] == 2
    dev = sum(p["device_s"] for p in r["programs"].values())
    assert 0 < r["busy_s"] <= dev + 1e-9 < r["window_s"]
    assert r["busy_s"] == pytest.approx(8.322e-6, rel=1e-3)
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "%convolution_tanh_fusion"
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"],
                                                               rel=1e-3)
    gaps = dict((n, s) for n, s in reversed(r["idle_gaps"]))
    assert r["idle_gaps"][0][0] == "bench.wait"
    assert gaps["bench.wait"] > 2e-3
    assert r["host_spans"]["bench.step"]["count"] == 3
    assert r["host_spans"]["bench.wait"]["count"] == 3
