"""Multi-tenant serving layer: fair scheduling, SLO instrumentation,
backpressure hints, and the asyncio front-end.

Scheduler-level tests run without jax (pure data structures); the
engine-level tests share one tiny module-scoped model. Front-end tests
drive the asyncio layer against a stub engine with an injected sleep, so
backoff behavior is asserted deterministically without wall-clock waits.
"""

import asyncio
import dataclasses
import json
import tempfile

import numpy as np
import pytest

import jax

from repro.configs.base import ModelConfig, SWMConfig
from repro.models.decoder import HybridDecoderLM
from repro.nn.module import init_params
from repro.serve.engine import (LatencyHistogram, Request, Scheduler,
                                ServeEngine)
from repro.serve.frontend import (SLO_CLASSES, AsyncFrontend, TenantConfig,
                                  TenantRejectedError, TokenBucket)
from repro.serve.guard import ManualClock, QueueFullError

jax.config.update("jax_platform_name", "cpu")

BATCH, CACHE = 2, 32


def _cfg(**kw):
    base = dict(name="tenants", n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=1, head_dim=16, d_ff=64, vocab=48, remat="none",
                param_dtype="float32", compute_dtype="float32",
                swm=SWMConfig(block_size=8, impl="dft"))
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def lm():
    cfg = _cfg()
    model = HybridDecoderLM(cfg)
    params = init_params(model.specs(), 0)
    return cfg, model, params


def _engine(lm, **kw):
    cfg, model, params = lm
    kw.setdefault("batch", BATCH)
    kw.setdefault("cache_len", CACHE)
    return ServeEngine(model, cfg, params, **kw)


def _reqs(seed, n, tenant="default", plen=5, max_new=4):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, 48, size=plen).astype(np.int32),
                    max_new=max_new, tenant=tenant) for _ in range(n)]


# ---------------------------------------------------------------------------
# Scheduler: fair policy (weighted DRR)
# ---------------------------------------------------------------------------


class TestFairScheduler:
    def test_weighted_round_robin_order(self):
        s = Scheduler("fair", tenant_weights={"a": 2, "b": 1})
        for i in range(6):
            s.submit(f"a{i}", 4, tenant="a")
        for i in range(3):
            s.submit(f"b{i}", 4, tenant="b")
        got = [s.take(1)[0] for _ in range(9)]
        # single-item takes advance the rotation each call and bank the
        # unused deficit; the 2:1 weight ratio is honored in aggregate
        assert got == ["a0", "b0", "a1", "b1", "a2", "b2", "a3", "a4", "a5"]
        assert got.count("b0") + got.count("b1") + got.count("b2") == 3
        # aggregate service over any full-rotation window follows weights
        assert [g[0] for g in got[:6]].count("a") == 3

    def test_starvation_free_under_heavy_tenant(self):
        s = Scheduler("fair", tenant_weights={"big": 4, "small": 1})
        for i in range(100):
            s.submit(f"big{i}", 4, tenant="big")
        s.submit("small0", 4, tenant="small")
        # the small tenant is served within one DRR round, not after the
        # heavy tenant's whole backlog
        first_10 = [s.take(1)[0] for _ in range(10)]
        assert "small0" in first_10

    def test_unknown_tenants_default_weight_one(self):
        s = Scheduler("fair")       # no weights: every tenant weight 1
        s.submit("x0", 4, tenant="x")
        s.submit("y0", 4, tenant="y")
        s.submit("x1", 4, tenant="x")
        assert [s.take(1)[0] for _ in range(3)] == ["x0", "y0", "x1"]

    def test_take_batch_spans_rounds(self):
        s = Scheduler("fair", tenant_weights={"a": 2, "b": 1})
        for i in range(4):
            s.submit(f"a{i}", 4, tenant="a")
        for i in range(2):
            s.submit(f"b{i}", 4, tenant="b")
        assert s.take(6) == ["a0", "a1", "b0", "a2", "a3", "b1"]

    def test_weights_require_fair_policy(self):
        with pytest.raises(ValueError, match="fair"):
            Scheduler("fifo", tenant_weights={"a": 2})

    def test_weights_must_be_positive(self):
        with pytest.raises(ValueError, match="weight"):
            Scheduler("fair", tenant_weights={"a": 0})

    def test_put_front_beats_rotation(self):
        s = Scheduler("fair", tenant_weights={"a": 1, "b": 1})
        s.submit("a0", 4, tenant="a")
        s.submit("b0", 4, tenant="b")
        s.put_front("a-deferred", 9, tenant="a")
        got = [s.take(1)[0] for _ in range(3)]
        assert got[0] == "a-deferred"

    def test_state_dict_round_trip_preserves_order(self):
        s = Scheduler("fair", tenant_weights={"a": 2, "b": 1})
        for i in range(5):
            s.submit(f"a{i}", 4, tenant="a")
        for i in range(3):
            s.submit(f"b{i}", 4, tenant="b")
        consumed = [s.take(1)[0] for _ in range(3)]
        blob = json.loads(json.dumps(s.state_dict()))  # snapshot wire format
        s2 = Scheduler("fair", tenant_weights={"a": 2, "b": 1})
        s2.load_state(blob)
        rest = [s2.take(1)[0] for _ in range(len(s2))]
        # the restored scheduler continues the EXACT rotation the
        # original would have taken
        assert consumed == ["a0", "b0", "a1"]
        assert rest == [s.take(1)[0] for _ in range(len(s))]

    def test_fifo_sjf_order_unchanged_by_tenant_field(self):
        # FIFO/SJF must ignore tenants entirely (bit-identical ordering)
        f = Scheduler("fifo")
        for i, t in enumerate(["a", "b", "a", "c"]):
            f.submit(i, 4 + i, tenant=t)
        assert f.take(4) == [0, 1, 2, 3]
        s = Scheduler("sjf")
        s.submit("long", 20, tenant="a")
        s.submit("short", 2, tenant="b")
        s.submit("mid", 10, tenant="a")
        assert s.take(3) == ["short", "mid", "long"]


# ---------------------------------------------------------------------------
# Satellite: drop-oldest under burst (O(log n) shed path)
# ---------------------------------------------------------------------------


class TestDropOldestBurst:
    @pytest.mark.timeout(60)
    def test_sustained_burst_keeps_newest_in_order(self):
        # regression: drop_oldest used to rescan + heapify the whole queue
        # per shed (O(n) each, quadratic under sustained overload). 20k
        # submissions against a 64-deep queue must both stay correct and
        # finish fast (the hard timeout catches a quadratic regression).
        s = Scheduler("fifo", max_queue=64, shed_policy="drop-oldest")
        for i in range(20_000):
            s.submit(i, 4)
        assert len(s) == 64
        assert s.take(64) == list(range(20_000 - 64, 20_000))

    @pytest.mark.timeout(60)
    def test_burst_under_sjf_drops_oldest_not_longest(self):
        s = Scheduler("sjf", max_queue=4, shed_policy="drop-oldest")
        for i, plen in enumerate([9, 1, 8, 2, 7]):
            s.submit(f"r{i}", plen)
        # r0 (oldest) was dropped regardless of its sjf key; the rest
        # drain in prompt-length order
        assert s.take(4) == ["r1", "r3", "r4", "r2"]

    def test_drop_oldest_interleaved_with_takes(self):
        s = Scheduler("fifo", max_queue=3, shed_policy="drop-oldest")
        s.submit("a", 4)
        s.submit("b", 4)
        assert s.take(1) == ["a"]         # lazy heap entry for "a" is dead
        s.submit("c", 4)
        s.submit("d", 4)
        s.submit("e", 4)                  # sheds "b" — not the dead "a"
        assert s.take(3) == ["c", "d", "e"]


# ---------------------------------------------------------------------------
# Satellite: put_front under sjf with interleaved purges
# ---------------------------------------------------------------------------


class TestPutFrontSJF:
    def test_reenters_ahead_of_same_key_entries(self):
        s = Scheduler("sjf")
        for i in range(3):
            s.submit(f"q{i}", 10)        # all the same sjf key
        s.submit("short", 2)
        deferred = s.take(1)             # sjf serves the short prompt first
        assert deferred == ["short"]
        # a deferred long-prompt request re-enters ahead of ALL same-key
        # queued entries, not behind them
        s.put_front("deferred-long", 10)
        assert s.take(1) == ["deferred-long"]
        assert s.take(3) == ["q0", "q1", "q2"]

    def test_survives_interleaved_purge(self):
        s = Scheduler("sjf")
        keep = []
        for i in range(4):
            s.submit(i, 10)
            keep.append(i)
        s.put_front(100, 10)
        # purge everything except the front item and two same-key entries
        s.purge(lambda item: item in {100, 1, 3})
        assert s.take(1) == [100], \
            "purge() must not demote a put_front entry behind same-key items"
        assert s.take(2) == [1, 3]

    def test_multiple_put_fronts_lifo_among_themselves(self):
        s = Scheduler("sjf")
        s.submit("q", 10)
        s.put_front("first", 10)
        s.purge(lambda item: True)        # no-op purge of live entries
        s.put_front("second", 10)
        assert s.take(3) == ["second", "first", "q"]


# ---------------------------------------------------------------------------
# Latency histograms (SLO instrumentation)
# ---------------------------------------------------------------------------


class TestLatencyHistogram:
    def test_quantiles_upper_bound_semantics(self):
        h = LatencyHistogram()
        for ms in (0.5, 1.5, 3.0, 40.0, 900.0):
            h.observe(ms)
        assert h.count == 5
        assert h.p50 >= 3.0            # the covering bucket's upper bound
        assert h.p99 >= 900.0
        assert h.quantile(0.2) >= 0.5

    def test_empty_histogram_has_no_quantiles(self):
        h = LatencyHistogram()
        assert h.p50 is None and h.p99 is None and h.count == 0

    def test_overflow_bucket_is_inf(self):
        h = LatencyHistogram()
        h.observe(1e9)
        assert h.p99 == float("inf")

    def test_counts_round_trip_exactly(self):
        h = LatencyHistogram()
        for ms in (0.01, 2.0, 2.0, 77.0, 1e4):
            h.observe(ms)
        h2 = LatencyHistogram(json.loads(json.dumps(list(h.counts))))
        assert list(h2.counts) == list(h.counts)
        assert h2.p50 == h.p50 and h2.p99 == h.p99

    def test_bad_counts_rejected_with_actionable_error(self):
        with pytest.raises(ValueError, match="bucket"):
            LatencyHistogram([1, 2, 3])


# ---------------------------------------------------------------------------
# Engine-level: tenant stats, TTFT through snapshot, retry hints,
# autosnapshot origin fix
# ---------------------------------------------------------------------------


class TestEngineTenancy:
    def test_per_tenant_stats_and_fair_service(self, lm):
        clk = ManualClock()
        eng = _engine(lm, policy="fair",
                      tenant_weights={"a": 2, "b": 1}, clock=clk)
        reqs = _reqs(0, 4, tenant="a") + _reqs(1, 2, tenant="b")
        rids = [eng.submit(r) for r in reqs]
        while eng.step():
            clk.advance(0.002)
        s = eng.stats
        assert s.tenants["a"].submitted == 4
        assert s.tenants["a"].completed == 4
        assert s.tenants["b"].completed == 2
        assert s.tenants["a"].tokens == 16 and s.tenants["b"].tokens == 8
        assert s.ttft_ms.count == 6
        for rid in rids:
            assert eng.poll(rid).status == "FINISHED"

    def test_invalid_tenant_rejected_at_request(self):
        with pytest.raises(ValueError, match="tenant"):
            Request(np.asarray([1, 2], np.int32), tenant="")

    def test_ttft_histograms_survive_snapshot_restore(self, lm):
        clk = ManualClock()
        with tempfile.TemporaryDirectory() as d:
            eng = _engine(lm, snapshot_dir=d, clock=clk,
                          tenant_weights=None)
            reqs = _reqs(2, 4, tenant="t0", max_new=6)
            rids = [eng.submit(r) for r in reqs]
            for _ in range(4):
                eng.step()
                clk.advance(0.002)
            assert eng.stats.ttft_ms.count > 0
            eng.snapshot()
            saved = list(eng.stats.ttft_ms.counts)
            saved_t = eng.stats.tenants["t0"].as_dict()

            eng2 = _engine(lm, snapshot_dir=d, clock=clk)
            eng2.restore()
            assert list(eng2.stats.ttft_ms.counts) == saved
            assert eng2.stats.tenants["t0"].as_dict() == saved_t
            while eng2.step():
                clk.advance(0.002)
            # the restored engine keeps observing into the same histograms
            assert eng2.stats.ttft_ms.count == 4

    def test_retry_after_hint_flows_from_drain_rate(self, lm):
        clk = ManualClock()
        eng = _engine(lm, max_queue=2, clock=clk)
        assert eng.retry_after_hint() is None   # nothing drained yet
        for r in _reqs(3, 4, max_new=2):
            try:
                eng.submit(r)
            except QueueFullError as e:
                assert e.retry_after_hint is None
        while eng.step():
            clk.advance(0.01)
        clk.advance(0.01)
        eng.step()      # one idle step: the last burst's drain registers
        assert eng.retry_after_hint() is not None       # rate observed
        for r in _reqs(4, 8, max_new=2):
            try:
                eng.submit(r)
            except QueueFullError as e:
                assert e.retry_after_hint is not None
                assert 1e-3 <= e.retry_after_hint <= 60.0
                break
        else:
            pytest.fail("queue bound never hit")
        eng.drain()

    def test_autosnapshot_skips_empty_engine(self, lm):
        with tempfile.TemporaryDirectory() as d:
            eng = _engine(lm, snapshot_dir=d, snapshot_every=1)
            for _ in range(3):
                eng.step()              # idle steps: nothing to snapshot
            assert eng.stats.snapshots == 0
            from repro.ft.checkpoint import latest_step
            assert latest_step(d) is None
            rids = [eng.submit(r) for r in _reqs(5, 2)]
            eng.step()
            assert eng.stats.snapshots > 0  # work present: snapshots resume
            eng.drain(rids)

    def test_restore_from_empty_snapshot_refused(self, lm):
        with tempfile.TemporaryDirectory() as d:
            eng = _engine(lm, snapshot_dir=d)
            eng.snapshot()              # explicit empty snapshot
            eng2 = _engine(lm, snapshot_dir=d)
            with pytest.raises(ValueError, match="EMPTY"):
                eng2.restore()


# ---------------------------------------------------------------------------
# Async front-end (stub engine, injected sleep: no wall-clock waits)
# ---------------------------------------------------------------------------


class _StubEngine:
    def __init__(self, reject_first=0, hint=None):
        self.reject_first = reject_first
        self.hint = hint
        self.submitted = []
        self._rid = 0

    def submit(self, request):
        if self.reject_first > 0:
            self.reject_first -= 1
            raise QueueFullError(5, 5, retry_after_hint=self.hint)
        self._rid += 1
        self.submitted.append(request)
        return self._rid

    def step(self):
        return False


def _fe(engine, clk=None, **kw):
    sleeps = []

    async def fake_sleep(s):
        sleeps.append(s)
        if clk is not None and s > 0:
            clk.advance(s)

    kw.setdefault("tenants", {
        "vip": TenantConfig("vip", slo="interactive", rate=10.0, burst=2),
        "bulk": TenantConfig("bulk", slo="batch", rate=100.0, burst=50),
    })
    fe = AsyncFrontend(engine, sleep=fake_sleep,
                       clock=(clk if clk is not None else (lambda: 0.0)),
                       **kw)
    return fe, sleeps


class TestAsyncFrontend:
    def test_slo_deadline_default_applied(self):
        eng = _StubEngine()
        fe, _ = _fe(eng)
        req = Request(np.asarray([1, 2, 3], np.int32))
        asyncio.run(fe.submit("vip", req))
        sub = eng.submitted[0]
        assert sub.tenant == "vip"
        assert sub.deadline_ms == SLO_CLASSES["interactive"].deadline_ms

    def test_explicit_deadline_not_overridden(self):
        eng = _StubEngine()
        fe, _ = _fe(eng)
        req = Request(np.asarray([1], np.int32), deadline_ms=123.0)
        asyncio.run(fe.submit("vip", req))
        assert eng.submitted[0].deadline_ms == 123.0

    def test_batch_class_keeps_no_deadline(self):
        eng = _StubEngine()
        fe, _ = _fe(eng)
        asyncio.run(fe.submit("bulk", Request(np.asarray([1], np.int32))))
        assert eng.submitted[0].deadline_ms is None

    def test_unregistered_tenant_rejected(self):
        fe, _ = _fe(_StubEngine())
        with pytest.raises(KeyError, match="unregistered"):
            asyncio.run(fe.submit("ghost",
                                  Request(np.asarray([1], np.int32))))

    def test_backoff_uses_retry_after_hint_proportionally(self):
        eng = _StubEngine(reject_first=3, hint=0.5)
        fe, sleeps = _fe(eng, max_retries=4, jitter=0.0)
        rid = asyncio.run(fe.submit("bulk",
                                    Request(np.asarray([1], np.int32))))
        assert rid == 1
        backoffs = [s for s in sleeps if s > 0]
        # hint * (attempt + 1): proportional, not constant spinning
        assert backoffs == [0.5, 1.0, 1.5]

    def test_exhausted_retries_raise_tenant_scoped(self):
        eng = _StubEngine(reject_first=99, hint=0.01)
        fe, _ = _fe(eng, max_retries=2, jitter=0.0)
        with pytest.raises(TenantRejectedError) as ei:
            asyncio.run(fe.submit("bulk",
                                  Request(np.asarray([1], np.int32))))
        assert ei.value.tenant == "bulk"
        assert ei.value.attempts == 3
        assert fe.rejections["bulk"] == 1

    def test_token_bucket_throttles_burst(self):
        clk = ManualClock()
        eng = _StubEngine()
        fe, sleeps = _fe(eng, clk=clk)
        # vip: rate 10/s, burst 2 — the 3rd submit must wait ~0.1 s
        async def burst():
            for _ in range(3):
                await fe.submit("vip", Request(np.asarray([1], np.int32)))
        asyncio.run(burst())
        waits = [s for s in sleeps if s > 0]
        assert waits and abs(waits[0] - 0.1) < 1e-6
        assert len(eng.submitted) == 3

    def test_tenant_weights_follow_slo_classes(self):
        fe, _ = _fe(_StubEngine())
        assert fe.tenant_weights() == {"vip": 4, "bulk": 1}

    def test_token_bucket_refills_on_clock(self):
        clk = ManualClock()
        b = TokenBucket(rate=2.0, burst=2, clock=clk)
        assert b.try_take() and b.try_take() and not b.try_take()
        assert abs(b.wait_time() - 0.5) < 1e-9
        clk.advance(0.5)
        assert b.try_take()

    def test_run_drives_engine_submissions_to_terminal(self, lm):
        eng = _engine(lm, policy="fair", tenant_weights={"vip": 1,
                                                         "bulk": 1})
        # both tenants on the batch class: no deadline defaults, so slow
        # CI interpret runs can never EXPIRE these requests
        fe = AsyncFrontend(eng, {
            "vip": TenantConfig("vip", slo="batch", rate=1e4, burst=100),
            "bulk": TenantConfig("bulk", slo="batch", rate=1e4, burst=100),
        })

        async def main():
            rids = []
            for i, r in enumerate(_reqs(6, 4, max_new=3)):
                rids.append(await fe.submit("vip" if i % 2 else "bulk", r))
            await fe.run(idle_rounds=2)
            return [await fe.result(rid) for rid in rids]

        states = asyncio.run(main())
        assert all(st.status == "FINISHED" for st in states)
        assert all(len(st.tokens) == 3 for st in states)


class TestLauncherTenantParsing:
    def _parse(self, text, default_slo="standard"):
        import argparse

        from repro.launch.serve import _parse_tenants
        ap = argparse.ArgumentParser()
        return _parse_tenants(ap, text, default_slo)

    def test_names_and_slos(self):
        out = self._parse("app:interactive,jobs:batch,web")
        assert sorted(out) == ["app", "jobs", "web"]
        assert out["app"].slo == "interactive"
        assert out["jobs"].slo == "batch"
        assert out["web"].slo == "standard"      # default fills in

    def test_empty_text_means_no_tenants(self):
        assert self._parse("") == {}

    def test_unknown_slo_class_errors(self):
        with pytest.raises(SystemExit):
            self._parse("app:gold")

    def test_duplicate_tenant_errors(self):
        with pytest.raises(SystemExit):
            self._parse("app,app:batch")

    def test_empty_entry_errors(self):
        with pytest.raises(SystemExit):
            self._parse("app,,jobs")
