"""Serving entry for latent-attention MoE decoders (DeepSeek-V2 family).

The run is ``serve.py``'s: the engine built the way
``repro.launch.serve.build_engine`` builds it, its executables prewarmed,
the window driven through ``submit`` / ``step`` / ``poll``, peak memory,
and the check of a sample of the finished requests against a float32
reference. This entry loads its own copy of that module and gives it what
it looks up by name when it runs:

* ``model_config`` — the program's ModelConfig from a DeepSeek-V2
  ``config.json``'s keys (latent attention, YaRN, the leading dense
  layers, routed and shared experts);
* ``reference`` — ``reference_mla.py`` in place of the dense reference;
* ``Tracer`` (``MLATracer``) — the trace reduced by ``trace_scopes``
  (engine spans and the model step's scopes, ``device_scopes``), plus
  ``moe_s``: the device time of the decode program's ops under the
  ``moe`` scope at any depth (router, top-k, the gathers of the experts'
  tables, the gate-weighted sum and the experts' projections inside
  it), which ``trace_scopes.SCOPES`` does not name. It is inclusive, not
  self time as the scopes of ``device_scopes`` are: the experts'
  projections count under ``circulant`` there too;
* ``warm_up`` — ``serve.py``'s, over the prompt buckets the traffic can
  use: the engine's last bucket (``cache_len``, 4096) takes no prompt of
  at most 1024 tokens, and each of its executables costs a compile;
* ``serve_window`` / ``check_outputs`` — the window also sums the wall
  time of the engine steps that launched a prefill (``prefill_step_s``
  in the result, read by ``metrics/prefill_share.py``); the check reads
  every request served tokens in the window, a request still running at
  the close with the tokens it had by then. Outputs of 512-2048 tokens
  outlast most of the 50 s window, so ``serve.py``'s check, which reads
  finished requests only, would read few.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import shutil
import time
from pathlib import Path
from typing import Dict, Optional, Sequence

import numpy as np

import reference_mla
import trace_reduce
import trace_scopes
from run import load_module

__all__ = ["model_config", "MLATracer", "Tracer", "PROGRAMS",
           "moe_seconds", "serve_window", "check_outputs", "warm_up", "run",
           "serve"]

serve = load_module(Path(__file__).with_name("serve.py"))


def model_config(cj: Dict):
    """The program's ModelConfig for a DeepSeek-V2 configuration file: the
    registry entry with every published number taken from the file."""
    from repro.configs.base import MLAConfig, SWMConfig, YarnConfig
    from repro.configs.registry import get_config

    y = cj["rope_scaling"]
    return dataclasses.replace(
        get_config(cj["registry"]),
        n_layers=cj["num_hidden_layers"], d_model=cj["hidden_size"],
        n_heads=cj["num_attention_heads"],
        n_kv_heads=cj["num_key_value_heads"], d_ff=cj["intermediate_size"],
        vocab=cj["vocab_size"],
        mla=MLAConfig(kv_lora_rank=cj["kv_lora_rank"],
                      qk_nope_head_dim=cj["qk_nope_head_dim"],
                      qk_rope_head_dim=cj["qk_rope_head_dim"],
                      v_head_dim=cj["v_head_dim"]),
        yarn=YarnConfig(factor=float(y["factor"]),
                        original_max_position=int(
                            y["original_max_position_embeddings"]),
                        beta_fast=float(y["beta_fast"]),
                        beta_slow=float(y["beta_slow"]),
                        mscale=float(y["mscale"]),
                        mscale_all_dim=float(y["mscale_all_dim"])),
        rope_theta=float(cj["rope_theta"]),
        n_experts=cj["n_routed_experts"],
        n_experts_per_token=cj["num_experts_per_tok"],
        d_ff_expert=cj["moe_intermediate_size"],
        dense_residual_ffn=cj["n_shared_experts"] > 0,
        d_ff_shared=cj["moe_intermediate_size"] * cj["n_shared_experts"],
        first_dense_layers=cj["first_k_dense_replace"],
        moe_every=cj["moe_layer_freq"],
        moe_norm_topk=cj["norm_topk_prob"],
        moe_routed_scale=float(cj["routed_scaling_factor"]),
        tie_embeddings=cj["tie_word_embeddings"],
        param_dtype=cj["param_dtype"], compute_dtype=cj["compute_dtype"],
        swm=SWMConfig(block_size=cj["swm_block_size"], impl=cj["swm_impl"]),
        sliding_window=0)


def _in_moe(tf_op: str) -> bool:
    for seg in tf_op.split("/"):
        m = trace_scopes._WRAP.match(seg)
        if (m.group(1) if m else seg) == "moe":
            return True
    return False


def moe_seconds(events: Sequence[trace_reduce.Ev],
                ops: Sequence[trace_scopes.Op], decode: str
                ) -> Optional[float]:
    """Device seconds of the decode program's ops under ``moe`` at any
    depth, in the traced window (each instant to the innermost op, as
    ``trace_scopes.reduce_scopes`` splits it), averaged over devices; None
    without a window span or a device plane."""
    wins = [e for e in events if e.name == trace_reduce.WINDOW_SPAN]
    planes = sorted({o.plane for o in ops})
    if not wins or not planes:
        return None
    lo, hi = wins[0].start, wins[0].end
    pat = re.compile(decode)
    total = 0.0
    for pl in planes:
        mods = trace_reduce.merge(
            (max(o.start, lo), min(o.end, hi)) for o in ops
            if o.plane == pl and o.line == trace_reduce.MODULES_LINE
            and pat.search(o.name) and o.end > lo and o.start < hi)
        line_ops = [(o.start, o.end, o) for o in ops if o.plane == pl
                    and o.line == trace_reduce.OPS_LINE]
        if not mods or not line_ops:
            continue
        inner = trace_scopes.innermost(line_ops, mods[0][0], mods[-1][1])
        total += sum(b - a for a, b, o in trace_scopes._overlap(inner, mods)
                     if o is not None and _in_moe(o.tf_op))
    return total / len(planes)


class MLATracer(serve.Tracer):
    def reduce(self):
        if self.dir is None:
            return None
        try:
            if self.state != 2:
                return None
            path = trace_reduce.find_xplane(self.dir)
            events = trace_reduce.load(path)
            out = trace_reduce.reduce_events(events, serve.PROGRAMS)
            if out is not None:
                ops = trace_scopes.read_device_ops(path)
                out.update(trace_scopes.reduce_scopes(
                    events, ops, serve.PROGRAMS["decode"]))
                out["moe_s"] = moe_seconds(events, ops,
                                           serve.PROGRAMS["decode"])
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


#: Wall seconds of the last window's engine steps that launched a
#: prefill (``serve_window`` sets it, ``run`` reports it).
_prefill_s = [None]


def serve_window(engine, mix, reqs, seconds, tracer):
    """``serve.serve_window``; a request still running at the close keeps
    the tokens the client had seen (one per ``tok_times``). Each
    ``engine.step()`` that launched a prefill adds its wall time to
    ``_prefill_s``."""
    step, spent = engine.step, [0.0]

    def timed_step():
        n, t = engine.stats.prefill_calls, time.perf_counter()
        out = step()
        if engine.stats.prefill_calls > n:
            spent[0] += time.perf_counter() - t
        return out

    engine.step = timed_step
    try:
        records, t0, t1 = _serve_window(engine, mix, reqs, seconds, tracer)
    finally:
        del engine.step
    _prefill_s[0] = spent[0]
    for r in records:
        if r.status is None:
            r.tokens = tuple(engine.poll(r.rid).tokens[:len(r.tok_times)])
    return records, t0, t1


def check_outputs(cj, mix, limits, specs, seed, records, t1, control=""):
    """``serve.check_outputs`` over every request served tokens in the
    window: one still running counts with the tokens that arrived by the
    close, as if it had asked for just those (it cannot be short; a failed
    one still fails)."""
    from repro.serve.guard import FINISHED

    def seen(r):
        k = sum(t <= t1 for t in r.tok_times)
        return dataclasses.replace(r, status=FINISHED, max_new=k,
                                   tokens=r.tokens[:k],
                                   tok_times=r.tok_times[:k])

    view = [seen(r) if r.status is None and r.tokens else r
            for r in records]
    return _check_outputs(cj, mix, limits, specs, seed, view, t1, control)


PROGRAMS = serve.PROGRAMS
Tracer = MLATracer
_serve_window, _check_outputs = serve.serve_window, serve.check_outputs
serve.model_config = model_config
serve.reference = reference_mla
serve.serve_window = serve_window
serve.check_outputs = check_outputs


def warm_up(engine, longest: int) -> None:
    """``serve.warm_up`` over the prompt buckets up to the first that
    holds a prompt of ``longest`` tokens."""
    from repro.serve.engine import Request

    top = min(b for b in engine.prompt_buckets if b >= longest)
    engine.prewarm(max_prompt_bucket=top)
    engine.generate([Request(np.zeros(max(1, b - 1), np.int32), max_new=2)
                     for b in engine.prompt_buckets if b <= top])


def run(cell: Dict, **kw) -> Dict:
    """``serve.run`` with this entry's ``Tracer`` (looked up here at each
    run, so a caller may replace it, as ``trace_breakdown.py`` does) and
    the warm-up of the buckets the cell's traffic uses."""
    serve.Tracer = Tracer
    serve.warm_up = functools.partial(
        warm_up, longest=int(cell["traffic"]["prompt_len"]["max"]))
    _prefill_s[0] = None
    res = serve.run(cell, **kw)
    res["prefill_step_s"] = _prefill_s[0]
    return res
