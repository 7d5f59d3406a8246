"""Serving entry: one model behind the program's continuous-batching engine.

Set-up builds ``repro.serve.engine.ServeEngine`` the way
``repro.launch.serve.build_engine`` does (``build_model(cfg)``, then the
engine over the weights, ``batch`` slots, ``cache_len`` and the mix's
prompt buckets), with the benchmark's seeded weights in place of the
launcher's ``load_params``. It compiles or loads every prefill and decode
executable (``prewarm``) and runs one short request per prompt bucket
through the host path.

The window then drives ``submit`` / ``step`` / ``poll`` from one thread:
an open loop sends each request at its due time, a closed loop keeps
``clients`` callers each waiting for its last reply. A token counts when
``poll`` first shows it. Each call sits in a ``bench.*`` host span, so a
trace can say what the host was doing in a device gap.

After the window: the device's peak memory is read, the engine is freed,
and a sample of the requests finished in the window (the longest among
them) goes to the float32 reference (``reference.py``). At each served
token's position the gap is the reference's best logit minus its logit
for the served token. ``correct`` holds when the mean gap over the checked
tokens is within the cell's limit, enough tokens were checked, and no
request failed or finished short. The widest gap and the share of tokens
off the reference's first choice are reported beside it (``readings``).
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Dict, List

import jax
import numpy as np

import clientstats
import loadgen
import reference
import trace_reduce
from weights import make_weights

__all__ = ["model_config", "build_engine", "warm_up", "serve_window",
           "check_outputs", "run", "PROGRAMS"]

# jitted programs of the engine, as the trace's module line names them
PROGRAMS = {"prefill": r"^jit_prefill\b", "decode": r"^jit_decode\b"}
TRACE_MAX_S = 5.0       # the trace covers the window's last seconds


def model_config(cj: Dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with every published number taken from the file."""
    from repro.configs.base import SWMConfig
    from repro.configs.registry import get_config

    base = get_config(cj["registry"])
    return dataclasses.replace(
        base,
        n_layers=cj["num_hidden_layers"], d_model=cj["hidden_size"],
        n_heads=cj["num_attention_heads"],
        n_kv_heads=cj["num_key_value_heads"], head_dim=cj["head_dim"],
        d_ff=cj["intermediate_size"], vocab=cj["vocab_size"],
        qk_norm=cj["qk_norm"], rope_theta=float(cj["rope_theta"]),
        tie_embeddings=cj["tie_word_embeddings"],
        param_dtype=cj["param_dtype"], compute_dtype=cj["compute_dtype"],
        swm=SWMConfig(block_size=cj["swm_block_size"],
                      impl=cj["swm_impl"]),
        sliding_window=0, n_experts=0)


def build_engine(cj: Dict, mix: Dict, seed: int):
    from repro.launch.specs import build_model
    from repro.serve.engine import ServeEngine

    cfg = model_config(cj)
    model = build_model(cfg)
    w = jax.block_until_ready(make_weights(model.specs(), seed))
    return ServeEngine(model, cfg, w, batch=mix["batch"],
                       cache_len=mix["cache_len"],
                       prompt_buckets=tuple(mix["prompt_buckets"]))


def warm_up(engine) -> None:
    """Every executable the mix can launch, then the host path once."""
    from repro.serve.engine import Request

    engine.prewarm()
    engine.generate([Request(np.zeros(max(1, b - 1), np.int32), max_new=2)
                     for b in engine.prompt_buckets])


def _greedy(r: loadgen.Req):
    from repro.serve.engine import Request, SamplingParams

    return Request(r.prompt, max_new=r.max_new,
                   sampling=SamplingParams(temperature=0.0))


class Tracer:
    """Starts the profiler a few seconds before the window closes and stops
    it at the close, so writing the trace holds up no request of the
    window; the traced stretch is the host span ``bench.window``."""

    def __init__(self, on: bool):
        self.on, self.state = on, 0
        self.start = self.stop = float("inf")
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.span = None
        self.t = (None, None)

    def arm(self, t0: float, seconds: float) -> None:
        self.stop = t0 + seconds
        self.start = self.stop - min(TRACE_MAX_S, 0.5 * seconds)

    def next_event(self) -> float:
        if not self.on or self.state == 2:
            return float("inf")
        return self.start if self.state == 0 else self.stop

    def tick(self, now: float) -> None:
        if self.on and self.state == 0 and now >= self.start:
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self.span.__enter__()
            self.t = (time.perf_counter(), None)
            self.state = 1
        elif self.state == 1 and now >= self.stop:
            self.finish()

    def finish(self) -> None:
        if self.state != 1:
            return
        self.t = (self.t[0], time.perf_counter())
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = 2

    def reduce(self):
        if self.dir is None:
            return None
        try:
            if self.state != 2:
                return None
            ev = trace_reduce.load(trace_reduce.find_xplane(self.dir))
            return trace_reduce.reduce_events(ev, PROGRAMS)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def serve_window(engine, mix: Dict, reqs: List[loadgen.Req],
                 seconds: float, tracer: Tracer):
    """Drive the engine for ``seconds``; returns the client's records and
    the window ``(t0, t1)``."""
    from jax.profiler import TraceAnnotation as span

    closed = mix["loop"] == "closed"
    clock = time.perf_counter
    records: List[clientstats.Record] = []
    live: Dict[int, clientstats.Record] = {}
    nxt = 0
    t0 = clock()
    t1 = t0 + seconds
    tracer.arm(t0, seconds)

    def send(r: loadgen.Req, due: float):
        with span("bench.submit"):
            rid = engine.submit(_greedy(r))
        rec = clientstats.Record(due=due, prompt_len=len(r.prompt),
                                 max_new=r.max_new, rid=rid, prompt=r.prompt)
        records.append(rec)
        live[rid] = rec

    if closed:
        for _ in range(int(mix["clients"])):
            send(reqs[nxt % len(reqs)], t0)
            nxt += 1
    while True:
        now = clock()
        tracer.tick(now)
        if now >= t1:
            break
        if not closed:
            while nxt < len(reqs) and t0 + reqs[nxt].due_s <= now:
                send(reqs[nxt], t0 + reqs[nxt].due_s)
                nxt += 1
        if not live:
            wake = t1
            if not closed and nxt < len(reqs):
                wake = min(wake, t0 + reqs[nxt].due_s)
            with span("bench.wait"):
                time.sleep(max(0.0, min(wake, tracer.next_event())
                               - clock()))
            continue
        with span("bench.step"):
            engine.step()
        now = clock()
        with span("bench.poll"):
            for rid, rec in list(live.items()):
                st = engine.poll(rid)
                k = len(st.tokens) - len(rec.tok_times)
                if k > 0:
                    rec.tok_times.extend([now] * k)
                if st.done:
                    rec.status, rec.tokens = st.status, st.tokens
                    del live[rid]
                    if closed:
                        send(reqs[nxt % len(reqs)], now)
                        nxt += 1
    tracer.finish()
    return records, t0, t1


def check_outputs(cj: Dict, mix: Dict, limits: Dict, specs, seed: int,
                  records, t1: float, control: str = "") -> Dict:
    """The reference's readings on a sample of the requests finished in
    the window: always the longest, the rest drawn from the seed.

    Returns the numbers compared with their limits, the readings, and with
    a ``control`` the same numbers with the control's gaps in place of the
    served tokens' (judged by ``judge`` as the program's are), else None."""
    from repro.serve.guard import FINISHED

    done = [r for r in records if r.status == FINISHED
            and r.tok_times and r.tok_times[-1] <= t1]
    failed = [r for r in records if r.status not in (None, FINISHED)]
    short = [r for r in done if len(r.tokens) != r.max_new]
    n = min(int(mix["check"]["sample"]), len(done))
    pick: List[clientstats.Record] = []
    if done:
        longest = max(done, key=lambda r: r.prompt_len + len(r.tokens))
        rest = [r for r in done if r is not longest]
        order = loadgen.rng_for(seed, 3).permutation(len(rest))
        pick = [longest] + [rest[i] for i in order[: n - 1]]
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            for r in pick]
    w = make_weights(specs, seed)
    served, ctl = reference.served_gaps(
        cj, w, seqs, [r.prompt_len for r in pick],
        pad_to=mix["cache_len"], batch=int(mix["check"]["ref_batch"]),
        control=control)
    del w
    readings = {"served": gap_stats(served)}

    def numbers(stats):
        return {
            "mean_gap": {"value": stats["mean"], "limit": limits["mean_gap"]},
            "checked_tokens": {"value": sum(len(r.tokens) for r in pick),
                               "limit": int(limits["min_checked_tokens"])},
            "failed_requests": {"value": len(failed), "limit": 0},
            "short_outputs": {"value": len(short), "limit": 0},
        }

    ctl_check = None
    if control:
        readings["control"] = gap_stats(ctl)
        ctl_check = numbers(readings["control"])
    return numbers(readings["served"]), readings, ctl_check


def gap_stats(per_seq) -> Dict:
    """Widest and mean gap over every checked token, the share of tokens
    that are not the reference's first choice, and where the widest one
    sat (request index, token index; token 0 came from prefill)."""
    if not per_seq:
        return {"widest": None, "mean": None, "off_share": None, "at": None}
    flat = np.concatenate(per_seq)
    i = int(np.argmax([g.max() for g in per_seq]))
    return {"widest": float(flat.max()), "mean": float(flat.mean()),
            "off_share": float((flat > 0).mean()),
            "at": [i, int(np.argmax(per_seq[i]))]}


def judge(check: Dict) -> bool:
    """Every number within its limit; too few tokens checked is a fail."""
    ok = True
    for k, c in check.items():
        v = c["value"]
        if v is None:
            ok = False
        elif k == "checked_tokens":
            ok &= v >= c["limit"]
        else:
            ok &= v <= c["limit"]
    return bool(ok)


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run(cell: Dict, *, seed: int, seconds: float, trace: bool,
        t_process: float, control: str = "") -> Dict:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cj, mix = cell["config"], cell["traffic"]
    marks = [("start", time.perf_counter())]
    engine = build_engine(cj, mix, seed)
    specs = engine.runner.specs()
    marks.append(("engine", time.perf_counter()))
    warm_up(engine)
    marks.append(("warm_up", time.perf_counter()))
    reqs = loadgen.make_requests(mix, seed, seconds, cj["vocab_size"])
    stats0 = dataclasses.replace(engine.stats)
    compiles0 = engine.prefill_compiles + engine.decode_compiles
    tracer = Tracer(trace)
    records, t0, t1 = serve_window(engine, mix, reqs, seconds, tracer)
    s = engine.stats
    counters = {k: getattr(s, k) - getattr(stats0, k)
                for k in ("decode_steps", "slot_steps_active",
                          "tokens_generated", "prefill_calls",
                          "padded_prompt_tokens", "decode_rows")}
    counters["batch"] = engine.batch
    counters["compiles_in_window"] = (engine.prefill_compiles
                                      + engine.decode_compiles - compiles0)
    mem = peak_bytes()
    tr = tracer.reduce()
    del engine, s, stats0
    gc.collect()
    check, readings, ctl_check = check_outputs(
        cj, mix, cell["limits"], specs, seed, records, t1, control=control)
    marks.append(("window", t0))
    return {
        "setup_s": t0 - t_process, "t0": t0, "t1": t1, "records": records,
        "setup_parts": {b[0]: b[1] - a[1] for a, b in
                        zip([("", t_process)] + marks, marks)},
        "attempted": len(records),
        "failed": check["failed_requests"]["value"],
        "memory_peak_bytes": mem, "counters": counters, "trace": tr,
        "trace_t": tracer.t, "config": cj, "traffic": mix,
        "check": check, "readings": readings, "correct": judge(check),
        "control_check": ctl_check,
        "control_correct": None if ctl_check is None else judge(ctl_check),
    }
