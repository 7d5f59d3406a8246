"""The serve engine's host spans, read back from a profiler trace: every
``serve.*`` phase sits inside its ``serve.step``, each decode step records
one launch, fetch and sample, and prefill spans appear only on steps that
admit."""

import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ModelConfig, SWMConfig
from repro.models.decoder import HybridDecoderLM
from repro.nn.module import init_params
from repro.serve.engine import Request, ServeEngine

jax.config.update("jax_platform_name", "cpu")

DECODE = ("serve.decode.prep", "serve.decode.launch", "serve.decode.fetch",
          "serve.decode.sample")
PREFILL = ("serve.prefill.launch", "serve.prefill.fetch",
           "serve.prefill.sample")
# (prompt length, max_new) of the traced requests
REQS = ((5, 3), (9, 5), (4, 2))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = ModelConfig(name="spans", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=1, head_dim=16, d_ff=64, vocab=48,
                      remat="none", param_dtype="float32",
                      compute_dtype="float32",
                      swm=SWMConfig(block_size=8, impl="dft"))
    model = HybridDecoderLM(cfg)
    eng = ServeEngine(model, cfg, init_params(model.specs(), 0), batch=2,
                      cache_len=32, prompt_buckets=(8, 16))
    eng.prewarm()
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, 48, n).astype(np.int32), max_new=m)
            for n, m in REQS]
    d = str(tmp_path_factory.mktemp("trace"))
    admits = {}
    with jax.profiler.trace(d):
        for r in reqs:
            eng.submit(r)
        while True:
            step = eng._step_count
            before = eng.stats.prefill_calls
            more = eng.step()
            admits[step] = eng.stats.prefill_calls - before
            if not more:
                break
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events
             if e.name.startswith("serve.")]
    return eng, spans, admits


def _by_step(spans):
    steps = sorted((s for s in spans if s[0] == "serve.step"),
                   key=lambda s: s[1])
    inside = defaultdict(list)
    for name, a, b, args in spans:
        if name == "serve.step":
            continue
        owner = [s for s in steps if s[1] <= a and b <= s[2]]
        assert len(owner) == 1, (name, a, b)
        assert args["step"] == owner[0][3]["step"], name
        inside[owner[0][3]["step"]].append(name)
    return steps, inside


def test_every_phase_lies_inside_its_step(traced):
    eng, spans, admits = traced
    steps, inside = _by_step(spans)
    assert [s[3]["step"] for s in steps] == sorted(admits)
    assert sum(len(v) for v in inside.values()) == sum(
        1 for s in spans if s[0] != "serve.step")


def test_one_launch_fetch_sample_per_decode_step(traced):
    eng, spans, admits = traced
    _, inside = _by_step(spans)
    launches = 0
    for step, names in inside.items():
        n = names.count("serve.decode.launch")
        assert n <= 1
        for phase in DECODE[1:]:
            assert names.count(phase) == n, (step, phase)
        launches += n
    assert launches == eng.stats.decode_steps
    rows = {a["rows"] for name, _, _, a in spans
            if name == "serve.decode.launch"}
    assert rows <= set(eng.decode_buckets)


def test_prefill_spans_only_on_admitting_steps(traced):
    eng, spans, admits = traced
    _, inside = _by_step(spans)
    for step, n in admits.items():
        names = inside.get(step, [])
        for phase in PREFILL:
            assert names.count(phase) == n, (step, phase)
        if n:
            assert "serve.admit" in names
    assert sum(admits.values()) == eng.stats.prefill_calls > 0


def test_launch_span_carries_the_rows_filled_positions(traced):
    """Each ``serve.decode.launch`` records in ``ctx`` the cache positions
    its rows have filled, the new entry's included; over the run they sum
    to ``EngineStats.decode_ctx_positions`` and to the hand count: a
    request of prompt p and n tokens decodes at positions p .. p + n - 2,
    each reading q + 1 (two slots, decode buckets 1 and 2: no pad lane)."""
    eng, spans, admits = traced
    ctx = [a["ctx"] for name, _, _, a in spans
           if name == "serve.decode.launch"]
    want = sum(q + 1 for p, n in REQS for q in range(p, p + n - 1))
    assert sum(ctx) == eng.stats.decode_ctx_positions == want
