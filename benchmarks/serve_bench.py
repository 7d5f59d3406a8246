"""Serving throughput: continuous-batching engine vs the wave baseline,
plus decode-side slot compaction vs full-slot decode.

Runs the same seeded request workload through ``ServeEngine`` (per-slot
admission, bucketed prefill shapes, compacted decode) in two decode
configurations — bucketed (default pow2 ``decode_buckets``) and full-slot
(``decode_buckets=(batch,)``, the pre-compaction behavior) — and through
``WaveEngine`` (fixed waves, stall-on-slowest), and reports:

  * tokens/sec (CPU wall time in this container — labeled as such),
  * tokens per decode step — the batching-efficiency signal that carries to
    hardware: the wave engine idles slots until the wave's largest max_new
    finishes, the continuous engine refills them;
  * decode rows per generated token — the decode-side work amplification:
    full-slot decode pays ``batch`` FFT -> o -> IFFT rows per step whatever
    the occupancy, compaction pays the bucket that holds the active set;
  * recompile counts — wave prefill recompiles per distinct wave length
    (unbounded in the workload), the continuous engine is bounded by its
    bucket grids on both the prefill and decode paths.

Three workloads: ``mixed`` (mixed prompt lengths and budgets — where wave
batching stalls), ``tail`` (tail-heavy: a few long-budget requests
outlive many short ones, so the batch drains to 1-2 live slots — where
full-slot decode burns dead rows), and ``prefix`` (many requests sharing
long prompt heads — the multi-turn / few-shot shape — where shared-prefix
KV reuse stops re-running prefill over heads other requests already
computed: the bench compares the continuous engine with the prefix cache
off vs on and reports ``prefill_tokens_saved`` / ``prefix_hit_rate`` /
prefill tokens per request / tokens-per-sec, asserting the saved-token
count is strictly positive and greedy outputs are bit-identical).
Greedy outputs of every engine are asserted identical before timing is
reported (same frozen-FFT(w) math, different orchestration); on the tail
workload the bucketed engine must show strictly lower decode row-work per
token than full-slot decode.

A fourth workload, ``chaos``, replays the mixed traffic under seeded
injected faults (transient launch failures, NaN-poisoned requests,
deadlines under a step stall, drop-oldest shedding, and an engine-fatal
fault recovered via snapshot/restore) and asserts the fault-tolerance
contract instead of timing: no hang, every request terminal, no slot or
refcount leak, unaffected outputs bit-identical, compile budget
unchanged.

A fifth workload, ``quantize``, serves the mixed traffic through fp32 vs
int8 frozen frequency tables vs a dequantized-table oracle engine and
asserts the quantized-serving contract: int8 greedy outputs bit-identical
to the oracle, resident frozen-table bytes at most 0.55x fp32, compile
budget unchanged.

A sixth workload, ``families``, serves the mixed traffic through three
model families behind their :class:`~repro.serve.runner.ModelRunner`
implementations — an attention decoder (``DecoderRunner``), an RWKV
recurrent stack (``RecurrentRunner``) and a capacity-bucketed MoE decoder
— and reports tokens/sec per family while asserting the cross-family
serving contract: every family stays inside its engine's compile budget,
and the recurrent family's bucketed greedy outputs are bit-identical to
the unbucketed B=1 loop through the same runner (the pad-invariance
guarantee that makes left-padded bucketed prefill legal for stateful
mixers).

    PYTHONPATH=src python benchmarks/serve_bench.py --quick --json out.json
    PYTHONPATH=src python benchmarks/serve_bench.py --quick --workload tail \
        --json out_tail.json
    PYTHONPATH=src python benchmarks/serve_bench.py --quick \
        --workload prefix --json out_prefix.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from benchmarks.common import emit
from repro.configs.base import ModelConfig, SWMConfig
from repro.models.decoder import HybridDecoderLM
from repro.nn.module import init_params
from repro.serve.engine import Request, ServeEngine, WaveEngine


def _cfg() -> ModelConfig:
    return ModelConfig(
        name="serve-bench", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab=128, remat="none",
        param_dtype="float32", compute_dtype="float32",
        swm=SWMConfig(block_size=8, impl="dft"),
    )


def _workload_mixed(n_requests: int, cache_len: int, seed: int):
    """Mixed prompt lengths AND mixed generation budgets — the shape of
    traffic where wave batching stalls (every wave runs to its max max_new
    at its max prompt length)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(2, 25))
        max_new = int(rng.integers(2, min(25, cache_len - plen)))
        reqs.append(Request(
            rng.integers(0, 128, size=plen).astype(np.int32),
            max_new=max_new,
        ))
    return reqs


def _workload_tail(n_requests: int, cache_len: int, seed: int):
    """Tail-heavy: most requests have tiny budgets, every 4th runs long —
    once the short ones finish and the queue empties, 1-2 live slots remain
    and full-slot decode pays ``batch`` rows for each of their tokens."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(2, 13))
        if i % 4 == 0:
            # long budget, clamped so plen + max_new - 1 <= cache_len stays
            # servable even at small --cache-len values
            cap = cache_len - plen + 1
            lo = max(2, min(cache_len // 2, cap - 1))
            max_new = int(rng.integers(lo, max(lo + 1, cap)))
        else:
            max_new = int(rng.integers(2, 5))
        reqs.append(Request(
            rng.integers(0, 128, size=plen).astype(np.int32),
            max_new=max_new,
        ))
    return reqs


def _workload_prefix(n_requests: int, cache_len: int, seed: int):
    """Shared-head traffic: every request is one of 3 long common heads
    (half the cache) plus a short private tail — the multi-turn / few-shot
    serving shape where the same prompt head is prefilled over and over
    unless resident rows are reused."""
    rng = np.random.default_rng(seed)
    head_len = cache_len // 2
    heads = [rng.integers(0, 128, size=head_len).astype(np.int32)
             for _ in range(3)]
    reqs = []
    for i in range(n_requests):
        tail = rng.integers(0, 128,
                            size=int(rng.integers(1, 4))).astype(np.int32)
        prompt = np.concatenate([heads[i % len(heads)], tail])
        cap = cache_len - prompt.shape[0] + 1
        max_new = int(rng.integers(2, max(3, min(7, cap))))
        reqs.append(Request(prompt, max_new=max_new))
    return reqs


WORKLOADS = {"mixed": _workload_mixed, "tail": _workload_tail,
             "prefix": _workload_prefix, "chaos": _workload_mixed,
             "quantize": _workload_mixed, "families": _workload_mixed,
             "tenants": _workload_mixed}


def _run_tenants(n_requests, batch, cache_len, seed, json_path):
    """Tenants workload: a bursty 3-tenant mix (SLO classes interactive/
    standard/batch -> DRR weights 4/2/1) served through the supervised
    engine with a mid-stream engine-fatal fault, asserting the
    multi-tenant robustness contract end to end:

      * fairness — at a DRR round boundary (every tenant still
        backlogged), each tenant's admitted share is within its weight
        +-1 request of its proportional share (starvation-free);
      * self-heal — the supervisor restores the latest snapshot onto a
        fresh engine and re-queues post-snapshot work; every request's
        incrementally-collected token stream is bit-identical to the
        fault-free run with zero duplicated or lost tokens
        (at-most-once emission);
      * SLO visibility — streaming TTFT histograms cover every request,
        survive snapshot/restore, and order by priority (the interactive
        tenant's p99 TTFT <= the batch tenant's under burst);
      * compile budget unchanged across the heal.

    All on a ManualClock (2 ms per engine step) so latency numbers are
    deterministic. Writes the tenants JSON report for CI (the BENCH
    trajectory artifact)."""
    from repro.serve.guard import ManualClock, ServeFaultInjector
    from repro.serve.supervisor import Supervisor
    import tempfile

    cfg = dataclasses.replace(_cfg(), name="serve-tenants")
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    weights = {"alpha": 4, "beta": 2, "gamma": 1}
    slo = {"alpha": "interactive", "beta": "standard", "gamma": "batch"}
    sum_w = sum(weights.values())
    n_per = max(8, n_requests // 3)
    rng = np.random.default_rng(seed)
    # uniform shapes: fairness accounting is request-count-based and the
    # per-stream greedy outputs must be comparable across runs
    reqs = [Request(rng.integers(0, 128, size=6).astype(np.int32),
                    max_new=5, tenant=t)
            for t in sorted(weights) for _ in range(n_per)]

    # fault-free baseline streams
    base_eng = ServeEngine(model, cfg, params, batch=batch,
                           cache_len=cache_len, policy="fair",
                           tenant_weights=weights)
    base_eng.prewarm()
    base = base_eng.generate(reqs)

    clk = ManualClock()
    inj = ServeFaultInjector(fatal_decode_at={20})
    with tempfile.TemporaryDirectory() as snap_dir:
        def factory():
            eng = ServeEngine(model, cfg, params, batch=batch,
                              cache_len=cache_len, policy="fair",
                              tenant_weights=weights, snapshot_dir=snap_dir,
                              snapshot_every=2, clock=clk,
                              fault_injector=inj)
            eng.prewarm()
            return eng

        sup = Supervisor(factory)
        budget_prefill = sup.engine.max_prefill_variants
        budget_decode = sup.engine.max_decode_variants
        srids = [sup.submit(r) for r in reqs]
        streams = {r: [] for r in srids}
        fair_at = None
        steps = 0
        while True:
            alive = sup.step()
            steps += 1
            clk.advance(0.002)
            for r in srids:
                new, _ = sup.take_new_tokens(r)
                streams[r].extend(new)
            admitted = {t: ts.admitted
                        for t, ts in sup.stats.tenants.items()}
            total = sum(admitted.values())
            # freeze the fairness window at the first DRR-round boundary
            # past two full rounds, while every tenant is still backlogged
            if fair_at is None and 2 * sum_w <= total <= 3 * n_per - 2:
                fair_at = dict(admitted)
            if not alive:
                break
            assert steps < 4000, "tenants workload hang"

        s = sup.stats
        # -- the multi-tenant contract -----------------------------------
        assert sup.restarts == 1, f"expected 1 self-heal, got {sup.restarts}"
        assert s.recoveries == 1, "snapshot restore did not run"
        assert fair_at is not None, "fairness window never observed"
        fair_total = sum(fair_at.values())
        starved = {}
        for t, w in weights.items():
            share = fair_total * w / sum_w
            if abs(fair_at.get(t, 0) - share) > w + 1:
                starved[t] = (fair_at.get(t, 0), share)
        assert not starved, (
            f"DRR fairness violated at admission boundary {fair_total}: "
            f"{starved} (admitted, proportional share)")
        dup_or_lost = [i for i, r in enumerate(srids)
                       if tuple(streams[r]) != tuple(base[i])]
        assert not dup_or_lost, (
            f"{len(dup_or_lost)} streams diverged from the fault-free "
            f"run across the heal (duplicated or lost tokens): "
            f"requests {dup_or_lost[:5]}")
        assert s.ttft_ms.count == len(reqs), (
            f"TTFT histogram covers {s.ttft_ms.count}/{len(reqs)} "
            f"requests (lost through snapshot/restore?)")
        p99_alpha = s.tenants["alpha"].ttft_ms.p99
        p99_gamma = s.tenants["gamma"].ttft_ms.p99
        assert p99_alpha <= p99_gamma, (
            f"SLO inversion under burst: interactive p99 TTFT "
            f"{p99_alpha}ms > batch {p99_gamma}ms")
        eng = sup.engine
        assert eng.prefill_compiles <= budget_prefill, "compile budget blown"
        assert eng.decode_compiles <= budget_decode, "compile budget blown"

        report = {
            "workload": {"name": "tenants", "n_per_tenant": n_per,
                         "batch": batch, "cache_len": cache_len,
                         "seed": seed, "weights": weights, "slo": slo,
                         "host": "cpu-interpret"},
            "injected": {"fatal_decode_at": [20]},
            "steps": steps,
            "restarts": sup.restarts,
            "fairness_at_boundary": {"admitted": fair_at,
                                     "total": fair_total},
            "ttft_ms": {"p50": s.ttft_ms.p50, "p99": s.ttft_ms.p99},
            "tenants": {t: ts.as_dict() for t, ts in s.tenants.items()},
            "contract": {
                "streams_bit_identical": True,
                "zero_duplicated_or_lost_tokens": True,
                "no_starvation": True,
                "ttft_serialized_through_snapshot": True,
                "compile_budget_unchanged": True,
            },
        }
    emit(f"serve/tenants_B{batch}_N{3 * n_per}", 0.0,
         f"steps={steps};restarts={sup.restarts};"
         f"fair_admitted={sorted(fair_at.items())};"
         f"ttft_p50={s.ttft_ms.p50}ms;ttft_p99={s.ttft_ms.p99}ms;"
         f"alpha_p99={p99_alpha}ms;gamma_p99={p99_gamma}ms;"
         f"streams_bit_identical=True;host=cpu")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


def _run_families(n_requests, batch, cache_len, seed, json_path):
    """Families workload: the same seeded mixed traffic served through
    three model families behind their ModelRunner implementations —
    an attention decoder (DecoderRunner), an RWKV recurrent stack
    (RecurrentRunner) and a capacity-bucketed no-drop MoE decoder.
    Reports tokens/sec per family and asserts the cross-family serving
    contract: each family's engine stays inside its compile budget, and
    the recurrent family's bucketed greedy outputs are bit-identical to
    the unbucketed B=1 loop through the same runner."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import LayerGroup, LayerSpec
    from repro.launch.specs import build_model
    from repro.serve.runner import make_runner

    base = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                vocab=128, remat="none", param_dtype="float32",
                compute_dtype="float32",
                swm=SWMConfig(block_size=8, impl="dft"))
    fams = {
        "decoder": ModelConfig(name="fam-decoder", n_layers=2, **base),
        "rwkv": ModelConfig(
            name="fam-rwkv", n_layers=2, rwkv_head_dim=16,
            rwkv_decay_lora=8, rwkv_mix_lora=8,
            groups=(LayerGroup(layers=(
                LayerSpec(mixer="rwkv", ffn="dense"),), repeat=2),),
            **base),
        "moe": ModelConfig(
            name="fam-moe", n_layers=2, n_experts=4, n_experts_per_token=2,
            d_ff_expert=128,
            groups=(LayerGroup(layers=(
                LayerSpec(mixer="attn", ffn="moe"),), repeat=2),),
            **base),
    }
    reqs = _workload_mixed(n_requests, cache_len, seed)
    warmup = _workload_mixed(max(4, n_requests // 4), cache_len, seed + 1)
    rows = {}
    rwkv_ctx = None
    for fam, cfg in fams.items():
        model = build_model(cfg)
        params = init_params(model.specs(), 0)
        eng = ServeEngine(model, cfg, params, batch=batch,
                          cache_len=cache_len)
        eng.prewarm()
        outs, row = _run(eng, warmup, reqs)
        assert eng.prefill_compiles <= eng.max_prefill_variants, (
            f"{fam}: prefill compile budget blown "
            f"({eng.prefill_compiles} > {eng.max_prefill_variants})")
        assert eng.decode_compiles <= eng.max_decode_variants, (
            f"{fam}: decode compile budget blown "
            f"({eng.decode_compiles} > {eng.max_decode_variants})")
        row["runner"] = type(eng.runner).__name__
        row["max_prefill_variants"] = eng.max_prefill_variants
        row["max_decode_variants"] = eng.max_decode_variants
        rows[fam] = row
        if fam == "rwkv":
            rwkv_ctx = (outs, eng.params, model, cfg)

    # pad-invariance: the recurrent family's bucketed engine outputs must
    # match the unbucketed B=1 loop through the same runner bit for bit
    outs_r, params_r, model_r, cfg_r = rwkv_ctx
    runner = make_runner(model_r, cfg_r, cache_len)
    check = reqs[:min(6, len(reqs))]
    prefill = jax.jit(runner.prefill)
    decode = jax.jit(runner.decode)
    ref = []
    for r in check:
        p = np.asarray(r.prompt, np.int32).reshape(-1)
        L = p.shape[0]
        state = runner.init_state(1)
        lg, _, state = prefill(
            params_r, jnp.asarray(p)[None],
            jnp.asarray(np.arange(L, dtype=np.int32))[None],
            state, jnp.asarray([0], np.int32))
        cur = int(np.argmax(np.asarray(lg)[0]))
        out, pos = [cur], L
        while len(out) < r.max_new:
            lg, _, state = decode(
                params_r, jnp.asarray([[cur]], np.int32), state,
                jnp.asarray([pos], np.int32), jnp.asarray([0], np.int32))
            cur = int(np.argmax(np.asarray(lg)[0]))
            out.append(cur)
            pos += 1
        ref.append(out)
    assert outs_r[:len(check)] == ref, (
        "rwkv bucketed serving diverged from the unbucketed B=1 runner "
        "loop: recurrent pad-invariance broken")

    report = {
        "workload": {"name": "families", "n_requests": n_requests,
                     "batch": batch, "cache_len": cache_len, "seed": seed,
                     "host": "cpu-interpret"},
        "families": rows,
        "recurrent_bucketed_equals_b1": True,
        "compile_budget_ok": True,
    }
    for fam, row in rows.items():
        emit(f"serve/family_{fam}_B{batch}_N{n_requests}",
             row["seconds"] * 1e6,
             f"runner={row['runner']};tok_s={row['tokens_per_sec']:.1f};"
             f"tok_per_decode_step={row['tokens_per_decode_step']:.2f};"
             f"prefill_compiles={row['prefill_compiles']}"
             f"<={row['max_prefill_variants']};"
             f"decode_compiles={row['decode_compiles']}"
             f"<={row['max_decode_variants']};host=cpu")
    emit("serve/families", 0.0,
         "recurrent_bucketed_equals_b1=True;compile_budget_ok=True;"
         + ";".join(f"{f}_tok_s={r['tokens_per_sec']:.1f}"
                    for f, r in rows.items()))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


def _run_chaos(n_requests, batch, cache_len, seed, json_path):
    """Chaos workload: the mixed workload served under seeded injected
    faults — a transient prefill launch failure, a transient decode launch
    failure (retried), NaN-poisoned prompts, per-request deadlines under an
    artificial step stall, drop-oldest load shedding, and an injected
    engine-fatal fault recovered via snapshot/restore into a replacement
    engine. Asserts the fault-tolerance contract end to end: the engine
    never hangs (hard step budget), every request reaches a terminal
    state, no slot or prefix-refcount leak, unaffected requests' greedy
    outputs are bit-identical to the fault-free run, and the compile
    budget is unchanged (the finiteness guard rides in the existing
    executables). Writes the chaos-run JSON report for CI."""
    import jax
    import jax.numpy as jnp

    from repro.serve.guard import (EngineFatalError, ManualClock,
                                   ServeFaultInjector, TERMINAL_STATES)
    import tempfile

    cfg = dataclasses.replace(_cfg(), name="serve-chaos",
                              tie_embeddings=False)
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    rng = np.random.default_rng(seed)
    # prompts drawn strictly below 100 so a poison token >= 100 can only
    # enter the model through the requests we poison on purpose
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(2, 25))
        max_new = int(rng.integers(2, min(25, cache_len - plen)))
        reqs.append(Request(
            rng.integers(0, 100, size=plen).astype(np.int32),
            max_new=max_new))

    def build(par, **kw):
        return ServeEngine(model, cfg, par, batch=batch,
                           cache_len=cache_len, **kw)

    # fault-free baseline (clean params, no injector)
    base_eng = build(params)
    base_eng.prewarm()
    base = base_eng.generate(reqs)
    used = {int(t) for o in base for t in o}
    poison_tok = next(t for t in range(cfg.vocab - 1, 99, -1)
                      if t not in used)
    params_poison = jax.tree.map(lambda x: x, params)
    params_poison["embed"]["table"] = (
        params_poison["embed"]["table"].at[poison_tok].set(jnp.nan))
    # clean requests behave bit-identically under the poisoned params:
    # the NaN embedding row is gather-only, and no clean prompt or
    # baseline output ever feeds it

    n_poison = max(1, n_requests // 6)
    poison_reqs = [Request(np.asarray([3, poison_tok, 7], np.int32),
                           max_new=4) for _ in range(n_poison)]
    # extra requests with a tight TTL, submitted AFTER the clean traffic so
    # drop-oldest shedding (which evicts the earliest submissions) cannot
    # reach them — the injected 1 s stall at step 7 blows their deadline
    # long before their 20-token budget completes
    n_deadline = 2
    deadline_reqs = [Request(np.asarray([5, 6, 7], np.int32), max_new=20,
                             deadline_ms=30.0) for _ in range(n_deadline)]
    max_queue = n_requests + n_deadline   # poison submits shed 2 clean reqs
    clk = ManualClock()
    inj = ServeFaultInjector(
        fail_prefill_at={1},            # one transient prefill fault
        fail_decode_at={2},             # one transient decode fault (retried)
        fatal_decode_at={8},            # engine-fatal -> snapshot/restore
        delay_at={7}, delay_s=1.0,      # step stall, past watchdog warmup
        clock=clk)
    eng_kw = dict(snapshot_every=2, max_queue=max_queue,
                  shed_policy="drop-oldest", clock=clk)
    with tempfile.TemporaryDirectory() as snap_dir:
        eng = build(params_poison, fault_injector=inj,
                    snapshot_dir=snap_dir, **eng_kw)
        eng.prewarm()
        budget_prefill = eng.max_prefill_variants
        budget_decode = eng.max_decode_variants
        rids = []
        for r in reqs + deadline_reqs + poison_reqs:
            rids.append(eng.submit(r))
        max_steps = 50 * (n_requests + n_deadline + n_poison) + 200
        steps = recoveries = slow_steps_seen = 0
        while True:
            if steps >= max_steps:
                raise AssertionError(
                    f"engine did not go idle within {max_steps} steps — "
                    f"hang detected")
            try:
                more = eng.step()
            except EngineFatalError:
                assert recoveries == 0, "second engine-fatal fault"
                recoveries += 1
                slow_steps_seen = max(slow_steps_seen, eng.stats.slow_steps)
                eng = build(params_poison, snapshot_dir=snap_dir, **eng_kw)
                eng.restore()
                continue
            steps += 1
            clk.advance(0.002)
            if not more:
                break
        slow_steps_seen = max(slow_steps_seen, eng.stats.slow_steps)

        statuses = {rid: eng.poll(rid) for rid in rids}
        hist: dict = {}
        for st in statuses.values():
            hist[st.status] = hist.get(st.status, 0) + 1
        # -- the chaos contract ------------------------------------------
        assert all(st.status in TERMINAL_STATES
                   for st in statuses.values()), "non-terminal request"
        assert not eng._active.any() and len(eng._sched) == 0, "not idle"
        assert (eng._slot_refs == 0).all(), "prefix refcount leak"
        assert not eng._req and not eng._out, "request-table leak"
        for (m, _), slot in eng._prefix_index.items():
            assert eng._slot_prompt[slot] is not None, "prefix index leak"
        mismatched = sum(
            1 for i, rid in enumerate(rids[:n_requests])
            if statuses[rid].status == "FINISHED"
            and list(statuses[rid].tokens) != base[i])
        assert mismatched == 0, (
            f"{mismatched} unaffected requests diverged from the "
            f"fault-free run")
        finished_clean = sum(
            1 for i, rid in enumerate(rids[:n_requests])
            if statuses[rid].status == "FINISHED")
        assert finished_clean > 0, "no clean request finished"
        for rid in rids[n_requests:n_requests + n_deadline]:
            assert statuses[rid].status == "EXPIRED", "deadline not enforced"
        for rid in rids[n_requests + n_deadline:]:
            assert statuses[rid].status == "FAILED", "poison not isolated"
            assert "non-finite" in (statuses[rid].error or "")
        assert eng.prefill_compiles <= budget_prefill, "compile budget blown"
        assert eng.decode_compiles <= budget_decode, "compile budget blown"
        assert eng.stats.recoveries == 1 and recoveries == 1
        assert eng.stats.aborted >= n_poison
        assert eng.stats.expired == n_deadline
        assert eng.stats.rejected >= 1, "drop-oldest shedding never fired"
        assert slow_steps_seen >= 1, "watchdog never flagged the stall"
        s = eng.stats
        report = {
            "workload": {"name": "chaos", "n_requests": n_requests,
                         "n_poison": n_poison, "n_deadline": n_deadline,
                         "batch": batch, "cache_len": cache_len,
                         "seed": seed, "poison_token": poison_tok,
                         "host": "cpu-interpret"},
            "injected": {"fail_prefill_at": [1], "fail_decode_at": [2],
                         "fatal_decode_at": [8], "delay_at": [7]},
            "steps": steps,
            "statuses": hist,
            "stats": s.as_dict(),
            "contract": {
                "all_terminal": True,
                "no_hang": True,
                "no_slot_or_refcount_leak": True,
                "unaffected_bit_identical": True,
                "poison_isolated": True,
                "compile_budget_unchanged": True,
                "recoveries": s.recoveries,
            },
        }
    emit(f"serve/chaos_B{batch}_N{n_requests}", 0.0,
         f"steps={steps};statuses={sorted(hist.items())};"
         f"aborted={s.aborted};expired={s.expired};rejected={s.rejected};"
         f"retries={s.launch_retries};recoveries={s.recoveries};"
         f"snapshots={s.snapshots};slow_steps={slow_steps_seen};"
         f"prefill_compiles={eng.prefill_compiles}"
         f"<=budget={budget_prefill};host=cpu")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


def _run(engine, warmup, reqs):
    """Warm the jit caches on a separate seeded mix, then time the measured
    workload (steady-state serving throughput). Compile counts are reported
    as the *delta during measurement*: the wave engine keeps compiling for
    every unseen wave length, the bucketed engine has a hard bound."""
    engine.generate(warmup)
    c0, s0 = engine.prefill_compiles, engine.stats.decode_steps
    a0, p0 = engine.stats.slot_steps_active, engine.stats.prefill_calls
    r0, t0 = engine.stats.decode_rows, engine.stats.tokens_generated
    h0, v0 = engine.stats.prefix_hits, engine.stats.prefill_tokens_saved
    l0 = engine.stats.prefix_lookups
    t_start = time.perf_counter()
    outs = engine.generate(reqs)
    dt = time.perf_counter() - t_start
    tokens = sum(len(o) for o in outs)
    decode_steps = engine.stats.decode_steps - s0
    active = engine.stats.slot_steps_active - a0
    decode_rows = engine.stats.decode_rows - r0
    gen_tokens = engine.stats.tokens_generated - t0
    lookups = engine.stats.prefix_lookups - l0
    return outs, {
        "tokens": tokens,
        "seconds": dt,
        "tokens_per_sec": tokens / max(dt, 1e-9),
        "decode_steps": decode_steps,
        "prefill_calls": engine.stats.prefill_calls - p0,
        "tokens_per_decode_step": active / max(decode_steps, 1),
        "decode_rows": decode_rows,
        "decode_rows_per_token": decode_rows / max(gen_tokens, 1),
        "decode_shapes": sorted(engine.stats.decode_shapes),
        "prefill_compiles_measured": engine.prefill_compiles - c0,
        "prefill_compiles": engine.prefill_compiles,
        "decode_compiles": engine.decode_compiles,
        "prefill_shapes": sorted(engine.stats.prefill_shapes),
        "prefix_hits": engine.stats.prefix_hits - h0,
        "prefix_lookups": lookups,
        "prefix_hit_rate": (engine.stats.prefix_hits - h0)
        / max(lookups, 1),
        "prefill_tokens_saved": engine.stats.prefill_tokens_saved - v0,
    }


def _run_prefix(model, cfg, params, reqs, warmup, n_requests, batch,
                cache_len, seed, json_path):
    """Prefix workload: continuous engine with the prefix cache OFF vs ON.
    Outputs must stay bit-identical; the cache-on engine must prefill
    strictly fewer prompt tokens per request (prefill_tokens_saved > 0)."""
    off = ServeEngine(model, cfg, params, batch=batch, cache_len=cache_len)
    off.prewarm()
    outs_off, row_off = _run(off, warmup, reqs)
    on = ServeEngine(model, cfg, params, batch=batch, cache_len=cache_len,
                     prefix_cache=True)
    on.prewarm()
    outs_on, row_on = _run(on, warmup, reqs)

    assert outs_on == outs_off, (
        "greedy outputs diverged with the prefix cache on: shared-head "
        "reuse must be bit-identical to full prefill"
    )
    assert row_on["prefill_tokens_saved"] > 0, (
        "prefix workload produced zero reused prefix tokens"
    )
    prompt_tokens = sum(r.prompt_len for r in reqs)
    for row in (row_off, row_on):
        row["prompt_tokens"] = prompt_tokens
        row["prefill_tokens"] = prompt_tokens - row["prefill_tokens_saved"]
        row["prefill_tokens_per_request"] = (
            row["prefill_tokens"] / n_requests)
    assert (row_on["prefill_tokens_per_request"]
            < row_off["prefill_tokens_per_request"]), (
        "prefill tokens/request must drop strictly with the prefix cache on"
    )

    report = {
        "workload": {"name": "prefix", "n_requests": n_requests,
                     "batch": batch, "cache_len": cache_len, "seed": seed,
                     "total_tokens": row_on["tokens"],
                     "prompt_tokens": prompt_tokens,
                     "host": "cpu-interpret"},
        "prefix_off": row_off,
        "prefix_on": row_on,
        "equal_greedy_outputs": True,
        "prefill_tokens_saved": row_on["prefill_tokens_saved"],
        "prefix_hit_rate": row_on["prefix_hit_rate"],
        "speedup_tokens_per_sec":
            row_on["tokens_per_sec"] / max(row_off["tokens_per_sec"], 1e-9),
        "prefill_token_drop":
            row_off["prefill_tokens_per_request"]
            / max(row_on["prefill_tokens_per_request"], 1e-9),
    }
    for name, row in (("prefix_off", row_off), ("prefix_on", row_on)):
        emit(f"serve/{name}_B{batch}_N{n_requests}_prefix",
             row["seconds"] * 1e6,
             f"tok_s={row['tokens_per_sec']:.1f};"
             f"prefill_tokens_per_request="
             f"{row['prefill_tokens_per_request']:.1f};"
             f"prefix_hits={row['prefix_hits']};"
             f"prefix_hit_rate={row['prefix_hit_rate']:.2f};"
             f"prefill_tokens_saved={row['prefill_tokens_saved']};"
             f"prefill_compiles={row['prefill_compiles']};"
             f"decode_compiles={row['decode_compiles']};host=cpu")
    emit("serve/speedup_prefix", 0.0,
         f"tokens_per_sec={report['speedup_tokens_per_sec']:.2f}x;"
         f"prefill_token_drop={report['prefill_token_drop']:.2f}x;"
         f"prefix_hit_rate={report['prefix_hit_rate']:.2f};"
         f"prefill_tokens_saved={report['prefill_tokens_saved']};"
         f"equal_outputs=True")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


def _run_quantize(model, cfg, params, reqs, warmup, n_requests, batch,
                  cache_len, seed, json_path):
    """Quantize workload: fp32 frozen tables vs int8 frozen tables vs the
    dequantized oracle (the int8 engine's tables dequantized back to fp32
    and served through a quantize-off engine).

    The contract asserted: int8 and oracle greedy outputs are BIT-identical
    (int8 -> f32 * scale is exact, so serving the quantized tables is
    serving the fake-quantized weights, not an approximation of them);
    resident frozen-table bytes drop to <= 0.55x fp32; and the compile
    budget is unchanged — quantization swaps array contents, never launch
    shapes or executable counts."""
    from repro.kernels.block_circulant.plan import dequantize_frozen

    fp = ServeEngine(model, cfg, params, batch=batch, cache_len=cache_len)
    fp.prewarm()
    outs_f, row_f = _run(fp, warmup, reqs)
    q = ServeEngine(model, cfg, params, batch=batch, cache_len=cache_len,
                    quantize="int8")
    q.prewarm()
    outs_q, row_q = _run(q, warmup, reqs)
    # oracle: the int8 engine's own tables, host-dequantized to fp32, served
    # through a quantize-off engine (freeze_params passes frozen trees
    # through untouched, so the oracle runs exactly these table values)
    oracle = ServeEngine(model, cfg, dequantize_frozen(q.params),
                         batch=batch, cache_len=cache_len)
    oracle.prewarm()
    outs_o, row_o = _run(oracle, warmup, reqs)

    assert outs_q == outs_o, (
        "int8 serving diverged from its dequantized-table oracle: "
        "in-engine dequant must be bit-identical"
    )
    bytes_f, bytes_q = fp.frozen_table_bytes(), q.frozen_table_bytes()
    ratio = bytes_q / max(bytes_f, 1)
    assert ratio <= 0.55, (
        f"int8 frozen tables are {ratio:.3f}x fp32 bytes (must be <= 0.55x)"
    )
    assert (row_q["prefill_compiles"] == row_f["prefill_compiles"]
            and row_q["decode_compiles"] == row_f["decode_compiles"]), (
        "quantization changed the compile budget: int8 tables must reuse "
        "the fp32 engine's executable counts"
    )
    for row, eng in ((row_f, fp), (row_q, q), (row_o, oracle)):
        row["frozen_table_bytes"] = eng.frozen_table_bytes()

    report = {
        "workload": {"name": "quantize", "n_requests": n_requests,
                     "batch": batch, "cache_len": cache_len, "seed": seed,
                     "total_tokens": row_q["tokens"],
                     "host": "cpu-interpret"},
        "fp32": row_f,
        "int8": row_q,
        "dequant_oracle": row_o,
        "int8_equals_oracle": True,
        "frozen_table_bytes_fp32": bytes_f,
        "frozen_table_bytes_int8": bytes_q,
        "frozen_table_bytes_ratio": ratio,
        "compile_budget_unchanged": True,
    }
    for name, row in (("fp32", row_f), ("int8", row_q),
                      ("dequant_oracle", row_o)):
        emit(f"serve/{name}_B{batch}_N{n_requests}_quantize",
             row["seconds"] * 1e6,
             f"tok_s={row['tokens_per_sec']:.1f};"
             f"frozen_table_bytes={row['frozen_table_bytes']};"
             f"prefill_compiles={row['prefill_compiles']};"
             f"decode_compiles={row['decode_compiles']};host=cpu")
    emit("serve/quantize_int8", 0.0,
         f"bytes_ratio={ratio:.3f};int8_equals_oracle=True;"
         f"compile_budget_unchanged=True;"
         f"tokens_per_sec_vs_fp32="
         f"{row_q['tokens_per_sec'] / max(row_f['tokens_per_sec'], 1e-9):.2f}x")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


def run(n_requests: int = 32, batch: int = 4, cache_len: int = 64,
        seed: int = 0, workload: str = "mixed", json_path: str = ""):
    if workload == "chaos":
        return _run_chaos(n_requests, batch, cache_len, seed, json_path)
    if workload == "families":
        return _run_families(n_requests, batch, cache_len, seed, json_path)
    if workload == "tenants":
        return _run_tenants(n_requests, batch, cache_len, seed, json_path)
    cfg = _cfg()
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    make = WORKLOADS[workload]
    reqs = make(n_requests, cache_len, seed)
    warmup = make(max(4, n_requests // 4), cache_len, seed + 1)
    if workload == "prefix":
        return _run_prefix(model, cfg, params, reqs, warmup, n_requests,
                           batch, cache_len, seed, json_path)
    if workload == "quantize":
        return _run_quantize(model, cfg, params, reqs, warmup, n_requests,
                             batch, cache_len, seed, json_path)

    wave = WaveEngine(model, cfg, params, batch=batch, cache_len=cache_len)
    outs_w, row_w = _run(wave, warmup, reqs)
    # full-slot decode: the PR-2 engine (decode always at the slot count)
    full = ServeEngine(model, cfg, params, batch=batch, cache_len=cache_len,
                       decode_buckets=(batch,))
    full.prewarm()
    outs_f, row_f = _run(full, warmup, reqs)
    # compacted decode: active slots gather into the smallest pow2 bucket
    cont = ServeEngine(model, cfg, params, batch=batch, cache_len=cache_len)
    cont.prewarm()        # finite bucket grids -> compile everything up front
    outs_c, row_c = _run(cont, warmup, reqs)

    assert outs_c == outs_w, "continuous and wave greedy outputs diverged"
    assert outs_c == outs_f, "bucketed and full-slot decode outputs diverged"
    for eng, row in ((full, row_f), (cont, row_c)):
        row["max_prefill_variants"] = eng.max_prefill_variants
        row["max_decode_variants"] = eng.max_decode_variants
        row["batch_buckets"] = list(eng.batch_buckets)
        row["prompt_buckets"] = list(eng.prompt_buckets)
        row["decode_buckets"] = list(eng.decode_buckets)

    row_work_drop = (row_f["decode_rows_per_token"]
                     / max(row_c["decode_rows_per_token"], 1e-9))
    if workload == "tail":
        assert (row_c["decode_rows_per_token"]
                < row_f["decode_rows_per_token"]), (
            "decode compaction must strictly drop row-work per token on the "
            "tail-heavy workload"
        )

    report = {
        "workload": {"name": workload, "n_requests": n_requests,
                     "batch": batch, "cache_len": cache_len, "seed": seed,
                     "total_tokens": row_c["tokens"],
                     "host": "cpu-interpret"},
        "wave": row_w,
        "continuous_full_slot": row_f,
        "continuous": row_c,
        "equal_greedy_outputs": True,
        "speedup_tokens_per_sec":
            row_c["tokens_per_sec"] / max(row_w["tokens_per_sec"], 1e-9),
        "speedup_tokens_per_decode_step":
            row_c["tokens_per_decode_step"]
            / max(row_w["tokens_per_decode_step"], 1e-9),
        "decode_row_work_drop_vs_full_slot": row_work_drop,
    }
    for name, row in (("wave", row_w), ("full_slot", row_f),
                      ("continuous", row_c)):
        emit(f"serve/{name}_B{batch}_N{n_requests}_{workload}",
             row["seconds"] * 1e6,
             f"tok_s={row['tokens_per_sec']:.1f};"
             f"tok_per_decode_step={row['tokens_per_decode_step']:.2f};"
             f"decode_rows_per_token={row['decode_rows_per_token']:.2f};"
             f"decode_steps={row['decode_steps']};"
             f"prefill_compiles_measured={row['prefill_compiles_measured']};"
             f"prefill_compiles={row['prefill_compiles']};"
             f"decode_compiles={row['decode_compiles']};host=cpu")
    emit(f"serve/speedup_{workload}", 0.0,
         f"tokens_per_sec={report['speedup_tokens_per_sec']:.2f}x;"
         f"tokens_per_decode_step="
         f"{report['speedup_tokens_per_decode_step']:.2f}x;"
         f"decode_row_work_drop={row_work_drop:.2f}x;"
         f"recompile_bound={row_c['max_prefill_variants']}"
         f"+{row_c['max_decode_variants']};"
         f"equal_outputs=True")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {json_path}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small workload (CI artifact)")
    ap.add_argument("--json", default="", help="write the report as JSON")
    ap.add_argument("--workload", choices=sorted(WORKLOADS),
                    default="mixed",
                    help="mixed: wave-stalling traffic; tail: tail-heavy "
                         "traffic where decode compaction pays off; "
                         "prefix: shared-prompt-head traffic where the "
                         "prefix cache skips repeated head prefill; "
                         "chaos: mixed traffic under seeded injected "
                         "faults, asserting the fault-tolerance contract; "
                         "quantize: mixed traffic through fp32 vs int8 "
                         "frozen tables vs the dequantized oracle "
                         "(bit-equality, bytes, compile budget); "
                         "families: the same traffic through decoder vs "
                         "rwkv vs moe runners (tokens/sec per family, "
                         "compile-budget + recurrent pad-invariance "
                         "asserts); "
                         "tenants: bursty 3-tenant mix through the "
                         "supervised fair engine with a mid-stream fatal "
                         "(DRR fairness, at-most-once streams, TTFT "
                         "histograms through snapshot/restore)")
    ap.add_argument("--n-requests", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    n = args.n_requests or (12 if args.quick else 32)
    run(n_requests=n, batch=args.batch, cache_len=args.cache_len,
        seed=args.seed, workload=args.workload, json_path=args.json)


if __name__ == "__main__":
    main()
