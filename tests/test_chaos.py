"""Chaos suite for the fault-tolerant serving path.

Drives every failure mode of ``ServeEngine`` deterministically — injected
launch faults (transient and fatal), NaN-poisoned requests, deadline
expiry on a manual clock, cancellation, load shedding, and
snapshot/restore — and asserts the robustness contract: no hang, every
request ends in exactly one terminal state, no slot or refcount leak,
unaffected requests' greedy outputs stay bit-identical to a fault-free
run, and the compile budget is unchanged (the finiteness guard rides in
the existing prefill/decode executables, no extra compiles).

NaN poisoning uses an untied-embedding config with one NaN row in the
embedding table: the row is gather-only, so exactly the requests that
feed the poison token see non-finite activations — per-request fault
isolation is testable without touching shared weights. The poison token
is chosen dynamically as one the fault-free baseline never emits (an
untrained model may generate any token id).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, SWMConfig
from repro.models.decoder import HybridDecoderLM
from repro.nn.module import init_params
from repro.serve.engine import (Request, SamplingParams, ServeEngine,
                                WaveEngine)
from repro.serve.guard import (CANCELLED, EXPIRED, FAILED, FINISHED,
                               TERMINAL_STATES, EngineFatalError,
                               InjectedFault, ManualClock, QueueFullError,
                               ServeFaultInjector)

jax.config.update("jax_platform_name", "cpu")

BATCH, CACHE = 2, 32


def _cfg(**kw):
    base = dict(name="chaos", n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=1, head_dim=16, d_ff=64, vocab=48, remat="none",
                param_dtype="float32", compute_dtype="float32",
                swm=SWMConfig(block_size=8, impl="dft"))
    base.update(kw)
    return ModelConfig(**base)


def _mix(seed, n, vocab=48, plen_hi=11, new_hi=7):
    rng = np.random.default_rng(seed)
    return [
        Request(rng.integers(0, vocab,
                             size=int(rng.integers(1, plen_hi))
                             ).astype(np.int32),
                max_new=int(rng.integers(1, new_hi)))
        for _ in range(n)
    ]


def _engine(lm, **kw):
    cfg, model, params = lm
    kw.setdefault("batch", BATCH)
    kw.setdefault("cache_len", CACHE)
    return ServeEngine(model, cfg, params, **kw)


def _drive(eng, clk=None, dt=0.0, max_steps=500):
    """Step to idle with a hard hang guard; optionally tick a ManualClock."""
    steps = 0
    while eng.step():
        steps += 1
        assert steps < max_steps, "engine did not go idle: hang"
        if clk is not None and dt:
            clk.advance(dt)
    return steps


def _no_leaks(eng):
    """Slot/refcount/queue invariants that must hold at idle regardless of
    how requests terminated."""
    assert not eng._active.any(), "slot leak: active mask not clear"
    assert (eng._slot_refs == 0).all(), "prefix refcount leak"
    assert len(eng._sched) == 0, "scheduler queue not drained"
    assert not eng._rid_slot, "rid->slot map leak"


@pytest.fixture(scope="module")
def lm():
    cfg = _cfg()
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    return cfg, model, params


@pytest.fixture(scope="module")
def base6(lm):
    """Fault-free outputs for the standard 6-request mix."""
    return _engine(lm).generate(_mix(0, 6))


@pytest.fixture(scope="module")
def poisoned():
    """Untied config + params with one NaN embedding row, the dynamically
    chosen poison token, and the fault-free baseline for a clean mix whose
    prompts never touch the poison row."""
    cfg = dataclasses.replace(_cfg(), name="chaos-nan",
                              tie_embeddings=False)
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    reqs = _mix(3, 5, vocab=40)      # prompts < 40: poison lives in 40..47
    base = _engine((cfg, model, params)).generate(reqs)
    used = {t for o in base for t in o}
    poison = next(t for t in range(cfg.vocab - 1, 39, -1) if t not in used)
    pp = jax.tree.map(lambda x: x, params)
    pp["embed"]["table"] = pp["embed"]["table"].at[poison].set(jnp.nan)
    return cfg, model, pp, poison, reqs, base


# ---------------------------------------------------------------------------
# Injected launch faults
# ---------------------------------------------------------------------------


def test_prefill_launch_failure_isolates_chunk(lm, base6):
    reqs = _mix(0, 6)
    inj = ServeFaultInjector(fail_prefill_at={0})
    eng = _engine(lm, fault_injector=inj)
    rids = [eng.submit(r) for r in reqs]
    _drive(eng)
    states = [eng.poll(rid) for rid in rids]
    assert all(s.status in TERMINAL_STATES for s in states)
    failed = [s for s in states if s.status == FAILED]
    assert failed and all("prefill launch failed" in s.error
                          for s in failed)
    # the fault killed exactly the first admitted chunk; everyone else runs
    # to completion bit-identically
    for s, b in zip(states, base6):
        if s.status == FINISHED:
            assert list(s.tokens) == b
    assert sum(s.status == FINISHED for s in states) == 6 - len(failed)
    assert eng.stats.aborted == len(failed)
    _no_leaks(eng)


def test_decode_launch_failure_retries_once(lm, base6):
    inj = ServeFaultInjector(fail_decode_at={1})
    eng = _engine(lm, fault_injector=inj)
    outs = eng.generate(_mix(0, 6))
    assert outs == base6, "retried decode launch must not perturb outputs"
    assert eng.stats.launch_retries == 1
    assert eng.stats.aborted == 0
    _no_leaks(eng)


class _AlwaysFailDecode(ServeFaultInjector):
    def on_launch(self, kind, index):
        if kind == "decode":
            raise InjectedFault(f"decode launch {index} always fails")


def test_decode_launch_failure_twice_is_fatal(lm):
    # a decode launch failing on the retry too -> donated cache can no
    # longer be trusted -> engine-fatal
    eng = _engine(lm, fault_injector=_AlwaysFailDecode())
    for r in _mix(0, 4):
        eng.submit(r)
    with pytest.raises(EngineFatalError):
        _drive(eng)
    # a dead engine refuses everything
    with pytest.raises(EngineFatalError):
        eng.submit(_mix(9, 1)[0])
    with pytest.raises(EngineFatalError):
        eng.step()


# ---------------------------------------------------------------------------
# NaN isolation (device-side finiteness guard)
# ---------------------------------------------------------------------------


def test_nan_prefill_aborts_only_poisoned_request(poisoned):
    cfg, model, pp, poison, reqs, base = poisoned
    eng = _engine((cfg, model, pp))
    bad = Request(np.asarray([3, poison, 7], np.int32), max_new=4)
    rids = [eng.submit(r) for r in reqs + [bad]]
    _drive(eng)
    sbad = eng.poll(rids[-1])
    assert sbad.status == FAILED
    assert "non-finite logits in prefill" in sbad.error
    assert sbad.tokens == ()
    for rid, b in zip(rids[:-1], base):
        s = eng.poll(rid)
        assert s.status == FINISHED and list(s.tokens) == b
    _no_leaks(eng)


def test_nan_decode_aborts_and_scrubs_slot(poisoned):
    cfg, model, pp, _, reqs, base = poisoned
    # poison the first token some request *generates* (and does not carry
    # in its prompt): the NaN enters when the token is fed back at the
    # next decode step, i.e. mid-stream, not at prefill
    victim = tok0 = None
    for v in range(len(reqs)):
        if len(base[v]) >= 2 and base[v][0] not in np.asarray(
                reqs[v].prompt):
            victim, tok0 = v, base[v][0]
            break
    assert victim is not None, "workload seed yields no decode-NaN victim"
    pp2 = jax.tree.map(lambda x: x, pp)
    pp2["embed"]["table"] = (
        pp2["embed"]["table"].at[tok0].set(jnp.nan))
    safe = [i for i in range(len(reqs))
            if i != victim and tok0 not in base[i]
            and tok0 not in np.asarray(reqs[i].prompt)]
    assert safe, "workload seed must leave at least one unpoisoned request"
    eng = _engine((cfg, model, pp2))
    rids = [eng.submit(r) for r in reqs]
    _drive(eng)
    s0 = eng.poll(rids[victim])
    assert s0.status == FAILED
    assert "non-finite logits in decode" in s0.error
    assert list(s0.tokens)[:1] == [tok0]      # partial progress kept
    for i in safe:
        s = eng.poll(rids[i])
        assert s.status == FINISHED and list(s.tokens) == base[i]
    _no_leaks(eng)
    # the poisoned slot was scrubbed (blank KV rows re-placed): reusing the
    # engine stays bit-identical for safe traffic
    again = eng.generate([reqs[i] for i in safe])
    assert again == [base[i] for i in safe]
    _no_leaks(eng)


def test_finiteness_guard_keeps_compile_budget(poisoned):
    cfg, model, pp, poison, reqs, _ = poisoned
    eng = _engine((cfg, model, pp))
    eng.prewarm()
    assert eng.prefill_compiles == eng.max_prefill_variants
    assert eng.decode_compiles == eng.max_decode_variants
    bad = Request(np.asarray([poison], np.int32), max_new=3)
    eng.generate(reqs + [bad])
    # the NaN check rides inside the existing executables: serving poisoned
    # traffic must not add a single compile
    assert eng.prefill_compiles == eng.max_prefill_variants
    assert eng.decode_compiles == eng.max_decode_variants


# ---------------------------------------------------------------------------
# Deadlines, cancellation, shedding
# ---------------------------------------------------------------------------


def test_deadline_expires_at_step_boundary(lm, base6):
    reqs = _mix(0, 6)
    clk = ManualClock()
    eng = _engine(lm, clock=clk)
    # request 0 gets a 5 ms TTL; each engine step takes a simulated 10 ms
    doomed = Request(reqs[0].prompt, max_new=reqs[0].max_new,
                     deadline_ms=5.0)
    rids = [eng.submit(r) for r in [doomed] + reqs[1:]]
    _drive(eng, clk=clk, dt=0.010)
    s0 = eng.poll(rids[0])
    assert s0.status == EXPIRED and "deadline_ms=5.0" in s0.error
    for rid, b in zip(rids[1:], base6[1:]):
        s = eng.poll(rid)
        assert s.status == FINISHED and list(s.tokens) == b
    assert eng.stats.expired == 1
    _no_leaks(eng)


def test_cancel_running_and_queued(lm):
    reqs = _mix(0, 6)
    eng = _engine(lm)
    rids = [eng.submit(r) for r in reqs]
    eng.step()                       # admit the first chunk
    running = next(r for r in rids if eng.poll(r).status == "RUNNING")
    queued = next(r for r in rids if eng.poll(r).status == "QUEUED")
    assert eng.cancel(running) and eng.cancel(queued)
    for rid in (running, queued):
        s = eng.poll(rid)
        assert s.status == CANCELLED and "cancelled by caller" in s.error
    assert eng.cancel(running) is False      # already terminal
    with pytest.raises(KeyError):
        eng.cancel(10_000)                   # unknown rid
    _drive(eng)                              # stale queue entry is skipped
    assert all(eng.poll(r).status in TERMINAL_STATES for r in rids)
    assert eng.stats.cancelled == 2
    _no_leaks(eng)


def test_reject_shedding_and_backpressure(lm, base6):
    reqs = _mix(0, 6)
    eng = _engine(lm, max_queue=2)
    for r in reqs[:2]:
        eng.submit(r)
    with pytest.raises(QueueFullError) as ei:
        eng.submit(reqs[2])
    assert ei.value.max_queue == 2 and ei.value.depth == 2
    assert eng.stats.rejected == 1
    _drive(eng)
    _no_leaks(eng)
    # generate() absorbs the backpressure internally: rejected submits step
    # the engine and retry, so outputs are complete and identical
    assert eng.generate(reqs) == base6


def test_drop_oldest_shedding(lm):
    reqs = _mix(0, 6)
    eng = _engine(lm, max_queue=2, shed_policy="drop-oldest")
    rids = [eng.submit(r) for r in reqs[:3]]      # third submit sheds first
    s0 = eng.poll(rids[0])
    assert s0.status == CANCELLED and "load shed (drop-oldest)" in s0.error
    assert eng.stats.rejected == 1
    _drive(eng)
    assert all(eng.poll(r).status == FINISHED for r in rids[1:])
    _no_leaks(eng)


# ---------------------------------------------------------------------------
# Snapshot / restore
# ---------------------------------------------------------------------------


def test_snapshot_restore_resumes_mid_stream(lm, tmp_path):
    cfg, model, params = lm
    reqs = _mix(0, 5)
    # include a sampled request so the snapshot must carry per-request RNG
    # state exactly, not just greedy determinism
    reqs.append(Request(np.asarray([1, 2, 3], np.int32), max_new=6,
                        sampling=SamplingParams(temperature=1.0, seed=7)))
    eng = _engine(lm, snapshot_dir=str(tmp_path))
    rids = [eng.submit(r) for r in reqs]
    for _ in range(3):
        eng.step()                   # decode a few tokens mid-stream
    eng.snapshot()
    assert eng.stats.snapshots == 1
    _drive(eng)
    want = {rid: eng.poll(rid) for rid in rids}

    twin = _engine(lm, snapshot_dir=str(tmp_path))
    twin.restore()
    assert twin.stats.recoveries == 1
    _drive(twin)
    for rid in rids:
        got = twin.poll(rid)
        assert got.status == want[rid].status
        assert got.tokens == want[rid].tokens, (
            "restored engine diverged mid-stream")
    _no_leaks(twin)


def test_restore_refuses_config_mismatch(lm, tmp_path):
    cfg, model, params = lm
    eng = _engine(lm, snapshot_dir=str(tmp_path))
    eng.submit(_mix(0, 1)[0])
    eng.snapshot()
    other = ServeEngine(model, cfg, params, batch=BATCH,
                        cache_len=CACHE * 2, snapshot_dir=str(tmp_path))
    with pytest.raises(ValueError, match="fingerprint"):
        other.restore()


def test_fatal_fault_recovers_via_snapshot(lm, base6, tmp_path):
    reqs = _mix(0, 6)
    inj = ServeFaultInjector(fatal_decode_at={3})
    eng = _engine(lm, fault_injector=inj, snapshot_dir=str(tmp_path),
                  snapshot_every=1)
    rids = [eng.submit(r) for r in reqs]
    with pytest.raises(EngineFatalError):
        _drive(eng)
    with pytest.raises(EngineFatalError):
        eng.snapshot()               # dead engines may not snapshot

    twin = _engine(lm, snapshot_dir=str(tmp_path))
    twin.restore()
    _drive(twin)
    for rid, b in zip(rids, base6):
        s = twin.poll(rid)
        assert s.status == FINISHED and list(s.tokens) == b, (
            "post-recovery outputs must be bit-identical to the "
            "fault-free run")
    assert twin.stats.recoveries == 1
    _no_leaks(twin)


def test_restore_reads_past_an_older_snapshots_token_gaps(lm, tmp_path):
    """Snapshots from before the engine stopped timing inter-token gaps
    carry a ``"tok"`` histogram and last-token ages; restore ignores
    them and resumes as from a current snapshot."""
    from repro.ft.checkpoint import restore_checkpoint, save_checkpoint

    eng = _engine(lm, snapshot_dir=str(tmp_path))
    rids = [eng.submit(r) for r in _mix(0, 3)]
    for _ in range(2):
        eng.step()
    step = eng._step_count
    eng.snapshot()
    ttft = eng.stats.ttft_ms.count
    state = restore_checkpoint(str(tmp_path), step)
    meta = json.loads(bytes(np.asarray(state["meta"])).decode("utf-8"))
    meta["stats_hists"]["tok"] = [1] * len(meta["stats_hists"]["ttft"])
    meta["timing"]["last_tok_age_s"] = [[rid, 0.01] for rid in rids]
    state["meta"] = np.frombuffer(json.dumps(meta).encode("utf-8"),
                                  np.uint8)
    save_checkpoint(str(tmp_path), step + 1, state)
    _drive(eng)

    twin = _engine(lm, snapshot_dir=str(tmp_path))
    assert twin.restore() == step + 1
    assert twin.stats.ttft_ms.count == ttft
    assert "tok" not in twin.stats.as_dict()
    _drive(twin)
    for rid in rids:
        assert twin.poll(rid).tokens == eng.poll(rid).tokens


def test_restore_needs_fresh_engine(lm, tmp_path):
    eng = _engine(lm, snapshot_dir=str(tmp_path))
    eng.submit(_mix(0, 1)[0])
    eng.snapshot()
    with pytest.raises(RuntimeError, match="fresh"):
        eng.restore()                # engine already has in-flight state


# ---------------------------------------------------------------------------
# Misc lifecycle contract
# ---------------------------------------------------------------------------


def test_wave_engine_rejects_deadlines(lm):
    cfg, model, params = lm
    wave = WaveEngine(model, cfg, params, batch=BATCH, cache_len=CACHE)
    with pytest.raises(ValueError, match="lifecycle"):
        wave.generate([Request(np.asarray([1, 2], np.int32), max_new=2,
                               deadline_ms=100.0)])


def test_bad_deadline_rejected(lm):
    eng = _engine(lm)
    with pytest.raises(ValueError):
        eng.submit(Request(np.asarray([1], np.int32), max_new=2,
                           deadline_ms=0.0))
    with pytest.raises(ValueError):
        eng.submit(Request(np.asarray([1], np.int32), max_new=2,
                           deadline_ms=-5.0))
