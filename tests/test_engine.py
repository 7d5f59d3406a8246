"""Continuous-batching serve engine: scheduling/bucketing correctness vs the
one-request-at-a-time reference loop, wave-engine equivalence, the
compile-budget + freeze-once regression, and cache-overflow errors."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, SWMConfig
from repro.models.decoder import HybridDecoderLM
from repro.nn.module import init_params
from repro.serve.engine import (Request, RequestState, SamplingParams,
                                Scheduler, ServeEngine, WaveEngine,
                                _sample_token, batch_split, make_decode_step,
                                make_prefill_step, pick_bucket, pow2_buckets,
                                validate_buckets)

jax.config.update("jax_platform_name", "cpu")


def _cfg(impl="dft", **kw):
    base = dict(name="eng", n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                head_dim=16, d_ff=64, vocab=48, remat="none",
                param_dtype="float32", compute_dtype="float32",
                swm=SWMConfig(block_size=8, impl=impl))
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def lm():
    cfg = _cfg()
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    return cfg, model, params


@pytest.fixture(scope="module")
def engine(lm):
    cfg, model, params = lm
    return ServeEngine(model, cfg, params, batch=2, cache_len=32)


def _mix(seed, n, vocab=48, plen_hi=11, new_hi=7):
    rng = np.random.default_rng(seed)
    return [
        Request(rng.integers(0, vocab,
                             size=int(rng.integers(1, plen_hi))
                             ).astype(np.int32),
                max_new=int(rng.integers(1, new_hi)))
        for _ in range(n)
    ]


def _reference_loop(model, cfg, params, requests, cache_len):
    """The gold loop: one request at a time, B=1, no padding, no buckets.
    Uses the same (frozen) params as the engine so any divergence is the
    engine's scheduling/bucketing — not numerics."""
    prefill = jax.jit(make_prefill_step(model, cfg))
    decode = jax.jit(make_decode_step(model, cfg))
    outs = []
    for r in requests:
        p = np.asarray(r.prompt, np.int32).reshape(-1)
        cache = model.init_cache(1, cache_len)
        logits, cache = prefill(params, jnp.asarray(p)[None], cache)
        lg = np.asarray(logits)[0]
        rng = r.sampling.make_rng()
        out, pos = [], len(p)
        while True:
            tok = _sample_token(lg, r.sampling, rng)
            if r.stop_tokens and tok in r.stop_tokens:
                break
            out.append(tok)
            if len(out) >= r.max_new:
                break
            logits, cache = decode(params, jnp.asarray([[tok]], np.int32),
                                   cache, jnp.asarray([pos], np.int32))
            lg = np.asarray(logits)[0]
            pos += 1
        outs.append(out)
    return outs


# ---------------------------------------------------------------------------
# Correctness vs the reference loop
# ---------------------------------------------------------------------------


def test_queued_requests_exceed_slots_mixed_lengths(lm, engine):
    """7 requests through 2 slots, mixed prompt lengths AND budgets: outputs
    must equal the unbatched reference, in request order."""
    cfg, model, _ = lm
    reqs = _mix(0, 7)
    outs = engine.generate(reqs)
    assert [len(o) for o in outs] == [r.max_new for r in reqs]
    assert outs == _reference_loop(model, cfg, engine.params, reqs, 32)


def test_stop_tokens_match_reference(lm, engine):
    cfg, model, _ = lm
    base = _mix(1, 4, new_hi=8)
    plain = engine.generate(base)
    # stop on a token each request actually produces mid-stream
    reqs = [
        Request(r.prompt, max_new=r.max_new,
                stop_tokens=(o[len(o) // 2],) if len(o) > 1 else (-1,))
        for r, o in zip(base, plain)
    ]
    outs = engine.generate(reqs)
    ref = _reference_loop(model, cfg, engine.params, reqs, 32)
    assert outs == ref
    for o, p in zip(outs, plain):
        assert len(o) <= len(p)


def test_sampling_reproducible_and_matches_reference(lm, engine):
    cfg, model, _ = lm
    rng = np.random.default_rng(3)
    reqs = [
        Request(rng.integers(0, 48, size=4).astype(np.int32), max_new=5,
                sampling=SamplingParams(temperature=0.8, top_k=8, seed=i))
        for i in range(4)
    ]
    a = engine.generate(reqs)
    b = engine.generate(reqs)
    assert a == b                       # per-request seeded rng
    assert a == _reference_loop(model, cfg, engine.params, reqs, 32)


def test_policies_produce_identical_outputs(lm, engine):
    """Slots are independent: sjf vs fifo only reorders admission, never
    changes any request's tokens."""
    cfg, model, params = lm
    reqs = _mix(4, 6)
    sjf = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                      policy="sjf")
    assert engine.generate(reqs) == sjf.generate(reqs)


def test_wave_and_continuous_identical_greedy(lm):
    """Acceptance: seeded request mix, wave == continuous, bit-identical."""
    cfg, model, params = lm
    reqs = _mix(5, 9, plen_hi=13, new_hi=9)
    cont = ServeEngine(model, cfg, params, batch=3, cache_len=32)
    wave = WaveEngine(model, cfg, params, batch=3, cache_len=32)
    assert cont.generate(reqs) == wave.generate(reqs)


# ---------------------------------------------------------------------------
# Decode-side bucketing: equivalence, row-work accounting, compile budget
# ---------------------------------------------------------------------------


def test_decode_bucket_equivalence_and_row_work(lm):
    """Slot compaction is a pure permutation: greedy outputs bit-identical
    across decode_buckets settings (full-slot = PR-2 behavior, pow2 default,
    all-singleton), while bucketed row-work strictly drops on a tail-heavy
    mix (one long request outlives the rest)."""
    cfg, model, params = lm
    reqs = _mix(10, 6, plen_hi=9, new_hi=4)
    reqs.append(Request(np.arange(5, dtype=np.int32), max_new=14))  # tail
    full = ServeEngine(model, cfg, params, batch=4, cache_len=32,
                       decode_buckets=(4,))
    bkt = ServeEngine(model, cfg, params, batch=4, cache_len=32)
    ones = ServeEngine(model, cfg, params, batch=4, cache_len=32,
                       decode_buckets=(1, 2, 3, 4))
    outs = full.generate(reqs)
    assert bkt.generate(reqs) == outs
    assert ones.generate(reqs) == outs
    assert outs == _reference_loop(model, cfg, full.params, reqs, 32)
    # same tokens, strictly less decode row-work once the batch tails off
    assert full.stats.tokens_generated == bkt.stats.tokens_generated
    assert bkt.stats.decode_rows < full.stats.decode_rows
    assert (bkt.stats.decode_rows_per_token
            < full.stats.decode_rows_per_token)
    assert set(full.stats.decode_shapes) == {4}
    assert min(bkt.stats.decode_shapes) < 4


def test_decode_compile_budget_bounded_by_buckets(lm):
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=4, cache_len=32,
                      prompt_buckets=(8, 16))
    eng.prewarm()
    assert eng.decode_compiles == len(eng.decode_buckets)
    assert eng.decode_compiles <= len(eng.batch_buckets)
    eng.generate(_mix(11, 9))
    eng.generate(_mix(12, 3))
    assert eng.decode_compiles == len(eng.decode_buckets)


# ---------------------------------------------------------------------------
# Shared-prefix KV reuse + donated decode buffers
# ---------------------------------------------------------------------------


def _shared_head_mix(seed, n, head_len=12, vocab=48, n_heads=2):
    """Requests drawn from a few long shared prompt heads + private tails —
    the workload shape the prefix cache exists for."""
    rng = np.random.default_rng(seed)
    heads = [rng.integers(0, vocab, size=head_len).astype(np.int32)
             for _ in range(n_heads)]
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab,
                            size=int(rng.integers(1, 5))).astype(np.int32)
        reqs.append(Request(np.concatenate([heads[i % n_heads], tail]),
                            max_new=int(rng.integers(2, 6))))
    return reqs


def _check_prefix_invariants(eng):
    """No dangling pins, and every index entry points at a slot that still
    holds the indexed prefix (eviction removed stale entries)."""
    assert (eng._slot_refs == 0).all()
    for (m, bts), slot in eng._prefix_index.items():
        p = eng._slot_prompt[slot]
        assert p is not None and p.shape[0] >= m
        assert p[:m].tobytes() == bts


def test_prefix_cache_bit_identical_shared_heads(lm):
    """Acceptance: shared-head traffic hits the prefix cache (tokens saved)
    while greedy outputs stay bit-identical to cache-off and to the
    unbatched reference loop."""
    cfg, model, params = lm
    reqs = _shared_head_mix(20, 9)
    off = ServeEngine(model, cfg, params, batch=3, cache_len=32)
    on = ServeEngine(model, cfg, params, batch=3, cache_len=32,
                     prefix_cache=True)
    outs = off.generate(reqs)
    assert on.generate(reqs) == outs
    assert outs == _reference_loop(model, cfg, off.params, reqs, 32)
    assert on.stats.prefix_hits > 0
    assert on.stats.prefill_tokens_saved > 0
    assert 0.0 < on.stats.prefix_hit_rate <= 1.0
    # cache-off engine never probes or saves anything
    assert off.stats.prefix_lookups == 0
    assert off.stats.prefill_tokens_saved == 0
    _check_prefix_invariants(on)


def test_prefix_cache_disjoint_workload_all_misses(lm):
    """Disjoint prompts: the index never matches, outputs are unchanged,
    and the saved-token counter stays zero (no false hits)."""
    cfg, model, params = lm
    reqs = _mix(21, 7)
    off = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    on = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                     prefix_cache=True, prefix_block=16)
    assert on.generate(reqs) == off.generate(reqs)
    assert on.stats.prefix_hits == 0
    assert on.stats.prefill_tokens_saved == 0
    _check_prefix_invariants(on)


def test_prefix_refcount_defers_instead_of_clobbering(lm):
    """Every queued request matches the SAME donor rows while placement is
    starved (2 slots, all free slots are donors): the refcount must keep
    the pinned donor out of placement/pad-lane reuse, deferral must keep
    the engine making progress, and outputs stay bit-identical."""
    cfg, model, params = lm
    head = np.arange(8, dtype=np.int32) + 3
    reqs = [Request(np.concatenate([head, np.asarray([40 + i], np.int32)]),
                    max_new=3) for i in range(6)]
    off = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    on = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                     prefix_cache=True)
    outs = off.generate(reqs)
    assert on.generate(reqs) == outs
    # round 1 (both slots empty) can't hit; everything admitted against a
    # resident donor afterwards must
    assert on.stats.prefix_hits >= 3
    assert on.stats.prefill_tokens_saved == 8 * on.stats.prefix_hits
    _check_prefix_invariants(on)


def test_prefix_capacity_bounds_index(lm):
    cfg, model, params = lm
    reqs = _shared_head_mix(22, 8, n_heads=3)
    off = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    on = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                     prefix_cache=True, prefix_capacity=2)
    assert on.generate(reqs) == off.generate(reqs)
    assert len(on._prefix_index) <= 2
    _check_prefix_invariants(on)
    with pytest.raises(ValueError, match="prefix_capacity"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    prefix_cache=True, prefix_capacity=0)
    with pytest.raises(ValueError, match="prefix_block"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    prefix_cache=True, prefix_block=0)


def test_prefix_cache_rejects_short_ring_caches():
    """A local-attention ring shorter than cache_len overwrites donor rows
    past the window — prefix reuse must refuse, not serve wrong tokens."""
    from repro.configs.base import LayerGroup, LayerSpec

    cfg = _cfg(sliding_window=8,
               groups=(LayerGroup(
                   layers=(LayerSpec(mixer="attn_local", ffn="dense"),),
                   repeat=2),))
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    with pytest.raises(ValueError, match="full-length KV caches"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    prefix_cache=True)
    # without prefix reuse the config still serves
    ServeEngine(model, cfg, params, batch=2, cache_len=32)


def test_donation_on_off_equivalence(lm):
    """donate_argnums is pure plumbing: outputs bit-identical with the
    cache donated or copied, with and without the prefix cache."""
    cfg, model, params = lm
    reqs = _mix(23, 6)
    d_on = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    d_off = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                        donate=False)
    assert d_on.donate and not d_off.donate
    assert d_on.generate(reqs) == d_off.generate(reqs)
    shared = _shared_head_mix(24, 6)
    p_on = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                       prefix_cache=True)
    p_off = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                        prefix_cache=True, donate=False)
    assert p_on.generate(shared) == p_off.generate(shared)
    assert p_on.stats.prefill_tokens_saved \
        == p_off.stats.prefill_tokens_saved > 0


def test_prewarm_commits_donated_caches_and_requires_idle(lm):
    """prewarm must COMMIT its warmed cache handles (a donated input buffer
    is dead after the call — the old discard behavior would kill the live
    cache), serve compile-free afterwards with outputs unchanged, and
    refuse to run over active slots."""
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                      prompt_buckets=(8, 16), prefix_cache=True)
    n = eng.prewarm()
    assert n == eng.max_prefill_variants + eng.max_decode_variants
    reqs = _shared_head_mix(25, 5)
    want = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                       prompt_buckets=(8, 16)).generate(reqs)
    assert eng.generate(reqs) == want
    assert eng.prefill_compiles == eng.max_prefill_variants
    assert eng.decode_compiles == eng.max_decode_variants
    # idle again: prewarm may rerun (no-op compiles, masked writes only)
    eng.prewarm()
    assert eng.generate(reqs) == want
    # active slots: refuse
    eng2 = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    eng2.submit(Request(np.arange(4, dtype=np.int32), max_new=6))
    eng2.step()
    with pytest.raises(RuntimeError, match="idle"):
        eng2.prewarm()
    eng2.drain()


def test_prefix_cache_compile_budget(lm):
    """Acceptance: with the prefix cache enabled the executable counts stay
    within max_prefill_variants + len(decode_buckets) — seeding rides in
    the same per-bucket executables, it never adds shapes."""
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=4, cache_len=32,
                      prompt_buckets=(8, 16), prefix_cache=True)
    eng.prewarm()
    eng.generate(_shared_head_mix(26, 10))
    eng.generate(_mix(27, 5))
    assert eng.prefill_compiles <= eng.max_prefill_variants
    assert eng.decode_compiles <= eng.max_decode_variants
    assert eng.max_decode_variants == len(eng.decode_buckets)


# ---------------------------------------------------------------------------
# Streaming submit / step / poll / drain
# ---------------------------------------------------------------------------


def test_streaming_submit_poll_matches_generate(lm):
    """The streaming loop and the closed generate() call produce identical
    tokens — generate IS the streaming loop (submit all, drain, reorder)."""
    cfg, model, params = lm
    reqs = _mix(13, 6, new_hi=8)
    want = ServeEngine(model, cfg, params, batch=2,
                       cache_len=32).generate(reqs)
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    rids = [eng.submit(r) for r in reqs]
    while eng.step():
        pass
    views = [eng.poll(rid) for rid in rids]
    assert all(v.done for v in views)
    assert [list(v.tokens) for v in views] == want
    done = eng.drain(rids)
    assert [done[rid] for rid in rids] == want


def test_streaming_incremental_poll_and_claim(lm):
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    rid = eng.submit(Request(np.arange(4, dtype=np.int32), max_new=6))
    v0 = eng.poll(rid)
    assert isinstance(v0, RequestState)
    assert v0 == RequestState(rid, False, ())          # queued, no tokens yet
    seen = [len(v0.tokens)]
    while eng.step():
        seen.append(len(eng.poll(rid).tokens))
    assert eng.poll(rid).done
    assert seen == sorted(seen) and len(eng.poll(rid).tokens) == 6
    # late submits keep the stream open and ids monotone
    rid2 = eng.submit(Request(np.arange(3, dtype=np.int32), max_new=2))
    assert rid2 > rid
    out = eng.drain()
    assert set(out) == {rid, rid2}
    assert len(out[rid]) == 6 and len(out[rid2]) == 2
    with pytest.raises(KeyError, match="already-claimed"):
        eng.poll(rid)
    with pytest.raises(KeyError, match="not a finished"):
        eng.drain([rid])


def test_drain_with_bad_id_claims_nothing(lm):
    """drain must validate every requested id before popping any: a bad id
    mid-list cannot silently discard other requests' outputs."""
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    rid = eng.submit(Request(np.arange(3, dtype=np.int32), max_new=2))
    while eng.step():
        pass
    with pytest.raises(KeyError, match="not a finished"):
        eng.drain([rid, 999])
    with pytest.raises(KeyError, match="duplicate"):
        eng.drain([rid, rid])
    # rid's output survived both failed drains and is still claimable
    assert len(eng.drain([rid])[rid]) == 2


def test_generate_with_invalid_request_enqueues_nothing(lm):
    """generate validates the whole batch before submitting any of it: a
    bad request must not leave its predecessors as ghost work that burns
    slots in the caller's next call."""
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    good = Request(np.arange(3, dtype=np.int32), max_new=2)
    bad = Request(np.arange(40, dtype=np.int32), max_new=2)
    with pytest.raises(ValueError, match="exceeds cache_len"):
        eng.generate([good, bad])
    assert not eng.step()                   # nothing queued, nothing active
    assert eng.stats.tokens_generated == 0


def test_generate_claims_only_its_own_requests(lm):
    """generate() drains the whole engine but only claims its own ids —
    an earlier streaming submit stays pollable afterwards."""
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    early = eng.submit(Request(np.arange(4, dtype=np.int32), max_new=3))
    outs = eng.generate(_mix(14, 3))
    assert len(outs) == 3
    v = eng.poll(early)
    assert v.done and len(v.tokens) == 3
    assert eng.drain([early]) == {early: list(v.tokens)}


# ---------------------------------------------------------------------------
# Compile budget + freeze-once regression (the plan-cache invariants)
# ---------------------------------------------------------------------------


def test_compile_budget_and_zero_rfft_after_freeze():
    from repro.kernels.block_circulant import ops
    from repro.kernels.block_circulant.plan import count_frozen_tables

    cfg = _cfg(impl="pallas")
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)

    n0 = ops.freq_weights_trace_count()
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=16,
                      prompt_buckets=(4, 8))
    n_frozen = count_frozen_tables(eng.params)
    assert n_frozen > 0
    # construction freezes each circulant table exactly once
    assert ops.freq_weights_trace_count() - n0 == n_frozen

    reqs = _mix(6, 5, plen_hi=7, new_hi=4)
    eng.generate(reqs)
    eng.generate(_mix(7, 3, plen_hi=4, new_hi=3))
    # zero rfft(w) across the entire serving lifetime after freeze
    assert ops.freq_weights_trace_count() - n0 == n_frozen

    # at most len(buckets) executables for prefill AND decode
    assert eng.prefill_compiles <= eng.max_prefill_variants
    assert eng.prefill_compiles == len(eng.stats.prefill_shapes)
    assert eng.decode_compiles <= eng.max_decode_variants
    assert eng.decode_compiles == len(eng.stats.decode_shapes)

    # structural check: the full per-surface contract set — NoFFT (pallas
    # impl promises zero fft, weights AND activations), no dense-fallback
    # contraction, no per-trace weight concat, frozen dtypes, donation
    # aliasing — over EVERY bucketed executable, via the auditor
    assert eng.audit(raise_on_violation=True) == []


def test_prewarm_compiles_every_bucket_then_serves_compile_free(lm):
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                      prompt_buckets=(8, 16))
    eng.prewarm()
    assert eng.prefill_compiles == eng.max_prefill_variants
    assert eng.decode_compiles == eng.max_decode_variants
    assert eng.max_decode_variants <= len(eng.batch_buckets)
    eng.generate(_mix(8, 5))
    assert eng.prefill_compiles == eng.max_prefill_variants
    assert eng.decode_compiles == eng.max_decode_variants


# ---------------------------------------------------------------------------
# Cache-overflow validation (no silent truncation)
# ---------------------------------------------------------------------------


def test_prompt_exceeding_cache_len_raises(lm, engine):
    with pytest.raises(ValueError, match="exceeds cache_len"):
        engine.generate([Request(np.arange(40, dtype=np.int32), max_new=1)])


def test_prompt_plus_max_new_exceeding_cache_len_raises(lm, engine):
    with pytest.raises(ValueError, match="ring cache would silently"):
        engine.generate([Request(np.arange(20, dtype=np.int32), max_new=20)])
    # boundary: the final token is returned but never written back, so
    # L + max_new - 1 == cache_len is servable
    outs = engine.generate([Request(np.arange(20, dtype=np.int32),
                                    max_new=13)])
    assert len(outs[0]) == 13


def test_wave_engine_also_validates(lm):
    cfg, model, params = lm
    wave = WaveEngine(model, cfg, params, batch=2, cache_len=32)
    with pytest.raises(ValueError, match="exceeds"):
        wave.generate([Request(np.arange(40, dtype=np.int32), max_new=1)])


def test_degenerate_requests_raise(lm, engine):
    with pytest.raises(ValueError, match="empty prompt"):
        engine.generate([Request(np.zeros((0,), np.int32))])
    with pytest.raises(ValueError, match="max_new"):
        engine.generate([Request(np.arange(3, dtype=np.int32), max_new=0)])
    # WaveEngine shares the same admission contract
    cfg, model, params = lm
    wave = WaveEngine(model, cfg, params, batch=2, cache_len=32)
    with pytest.raises(ValueError, match="max_new"):
        wave.generate([Request(np.arange(3, dtype=np.int32), max_new=0)])


def test_wave_engine_is_greedy_only(lm):
    cfg, model, params = lm
    wave = WaveEngine(model, cfg, params, batch=2, cache_len=32)
    with pytest.raises(ValueError, match="greedy-only"):
        wave.generate([Request(np.arange(3, dtype=np.int32), max_new=2,
                               sampling=SamplingParams(temperature=0.5))])
    with pytest.raises(ValueError, match="greedy-only"):
        wave.generate([Request(np.arange(3, dtype=np.int32), max_new=2,
                               stop_tokens=(1,))])


def test_recurrent_mixer_capabilities():
    """Recurrent families serve through ServeEngine's RecurrentRunner
    (pad-aware masking makes bucketed prefill safe), but their state has
    no per-position rows: the prefix cache must refuse with an actionable
    message, and the padding wave baseline still rejects batched waves."""
    from repro.configs.base import LayerGroup, LayerSpec
    from repro.serve.runner import RecurrentRunner

    cfg = _cfg(n_layers=1, rwkv_head_dim=16, rwkv_decay_lora=8,
               rwkv_mix_lora=8,
               groups=(LayerGroup(
                   layers=(LayerSpec(mixer="rwkv", ffn="dense"),),
                   repeat=1),))
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    assert isinstance(eng.runner, RecurrentRunner)
    assert not eng.runner.supports_prefix_cache
    outs = eng.generate([Request(np.arange(1, 5, dtype=np.int32), max_new=3),
                         Request(np.arange(2, 9, dtype=np.int32), max_new=3)])
    assert all(len(o) == 3 for o in outs)
    # recurrent state has no per-position rows -> prefix reuse impossible
    with pytest.raises(ValueError, match="recurrent state"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    prefix_cache=True)
    # the wave baseline has no validity masking: batched waves still refuse
    with pytest.raises(ValueError, match="recurrent state"):
        WaveEngine(model, cfg, params, batch=2, cache_len=32)
    # a wave of one never pads: still allowed
    WaveEngine(model, cfg, params, batch=1, cache_len=32)


# ---------------------------------------------------------------------------
# Scheduler / bucket unit behavior
# ---------------------------------------------------------------------------


def test_scheduler_orders():
    fifo = Scheduler("fifo")
    sjf = Scheduler("sjf")
    for name, plen in (("a", 5), ("b", 1), ("c", 3)):
        fifo.submit(name, plen)
        sjf.submit(name, plen)
    assert fifo.take(3) == ["a", "b", "c"]
    assert sjf.take(3) == ["b", "c", "a"]
    with pytest.raises(ValueError):
        Scheduler("lifo")


def test_bucket_helpers():
    assert pow2_buckets(8, 64) == (8, 16, 32, 64)
    assert pow2_buckets(8, 48) == (8, 16, 32, 48)
    assert pow2_buckets(1, 1) == (1,)
    assert pick_bucket(9, (8, 16, 32)) == 16
    assert pick_bucket(8, (8, 16, 32)) == 8
    with pytest.raises(ValueError):
        pick_bucket(33, (8, 16, 32))
    assert batch_split(7, (1, 2, 4)) == [4, 2, 1]
    assert batch_split(4, (1, 2, 4)) == [4]
    # any m <= slot count decomposes exactly
    for m in range(1, 17):
        assert sum(batch_split(m, (1, 2, 4, 8))) == m


def test_batch_split_without_unit_bucket_raises():
    """A bucket list that cannot cover the remainder must raise a ValueError
    naming the buckets — not leak a bare StopIteration from next()."""
    with pytest.raises(ValueError, match=r"\[2, 4\].*include 1"):
        batch_split(3, (2, 4))
    with pytest.raises(ValueError, match="cannot decompose 5"):
        batch_split(5, (4,))


def test_validate_buckets_and_engine_construction(lm):
    assert validate_buckets("b", (4, 1, 2, 2), 4) == (1, 2, 4)
    assert validate_buckets("b", (2,), 4) == (2, 4)      # hi appended
    with pytest.raises(ValueError, match="decode_buckets"):
        validate_buckets("decode_buckets", (0, 2), 4)
    with pytest.raises(ValueError, match="decode_buckets"):
        validate_buckets("decode_buckets", (8,), 4)
    with pytest.raises(ValueError, match="decode_buckets"):
        validate_buckets("decode_buckets", (), 4)
    # engine construction validates user-supplied buckets the same way
    cfg, model, params = lm
    with pytest.raises(ValueError, match="decode_buckets"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    decode_buckets=(3,))
    with pytest.raises(ValueError, match="prompt_buckets"):
        ServeEngine(model, cfg, params, batch=2, cache_len=32,
                    prompt_buckets=(0, 8))
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32,
                      decode_buckets=(1,))
    assert eng.decode_buckets == (1, 2)                  # batch appended


def test_top_k_ties_keep_exactly_k():
    """Regression: `z >= kth` kept every candidate tied at the k-th value.
    Ties now break deterministically toward the lower token id, so exactly
    top_k survive."""
    logits = np.zeros(8, np.float32)
    logits[[2, 4, 6]] = 1.0                # three-way tie at the top
    sp = SamplingParams(temperature=1.0, top_k=2, seed=0)
    draws = {_sample_token(logits, sp, np.random.default_rng(s))
             for s in range(200)}
    # survivors are the two LOWEST tied ids; 6 (and everything cold) is out
    assert draws == {2, 4}
    # k-th value tied with below-threshold entries: still exactly k
    tied = np.array([3.0, 2.0, 2.0, 2.0, 0.0], np.float32)
    sp1 = SamplingParams(temperature=1.0, top_k=2)
    draws = {_sample_token(tied, sp1, np.random.default_rng(s))
             for s in range(200)}
    assert draws == {0, 1}


def test_top_k_at_least_vocab_means_full_vocab():
    """top_k >= vocab is explicitly full-vocab sampling: identical draws to
    top_k=0 under the same rng stream."""
    rng = np.random.default_rng(5)
    logits = rng.normal(size=16).astype(np.float32)
    for k in (16, 17, 1000):
        sp_k = SamplingParams(temperature=0.9, top_k=k)
        sp_0 = SamplingParams(temperature=0.9, top_k=0)
        a = [_sample_token(logits, sp_k, np.random.default_rng(s))
             for s in range(50)]
        b = [_sample_token(logits, sp_0, np.random.default_rng(s))
             for s in range(50)]
        assert a == b


def test_request_defaults_and_stop_token_normalization():
    """Each Request gets its own SamplingParams (default_factory, no shared
    mutable-ish default), and stop_tokens normalizes to a tuple."""
    a, b = Request(np.arange(3, dtype=np.int32)), \
        Request(np.arange(3, dtype=np.int32))
    assert a.sampling == SamplingParams() and a.sampling is not b.sampling
    r = Request(np.arange(3, dtype=np.int32), stop_tokens=[7, 9])
    assert r.stop_tokens == (7, 9) and isinstance(r.stop_tokens, tuple)
    # list- and array-valued stop_tokens hash/compare like the tuple form
    assert Request(np.arange(2, dtype=np.int32),
                   stop_tokens=np.array([1, 2])).stop_tokens == (1, 2)
    assert all(isinstance(t, int) for t in r.stop_tokens)


def test_list_stop_tokens_served_like_tuple(lm, engine):
    cfg, model, _ = lm
    base = Request(np.arange(4, dtype=np.int32), max_new=6)
    plain = engine.generate([base])[0]
    assert len(plain) > 1
    stop = plain[len(plain) // 2]
    with_list = Request(np.arange(4, dtype=np.int32), max_new=6,
                        stop_tokens=[stop])
    with_tuple = Request(np.arange(4, dtype=np.int32), max_new=6,
                         stop_tokens=(stop,))
    assert (engine.generate([with_list])
            == engine.generate([with_tuple]))


def test_stats_accounting(lm):
    cfg, model, params = lm
    eng = ServeEngine(model, cfg, params, batch=2, cache_len=32)
    reqs = _mix(9, 4)
    outs = eng.generate(reqs)
    s = eng.stats
    assert s.tokens_generated == sum(len(o) for o in outs)
    assert s.requests_completed == len(reqs)
    assert s.prefill_calls >= 1 and s.decode_steps >= 1
    assert 0.0 < s.tokens_per_decode_step <= eng.batch
    d = s.as_dict()
    assert d["prefill_shapes"] == sorted(s.prefill_shapes)
