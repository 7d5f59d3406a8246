"""CPU tests of the engine-span and model-step-scope reduction
(``trace_scopes``): synthetic events, the ``tf_op`` reader on recorded TPU
traces, and the readers of the per-decode-launch metrics."""

from __future__ import annotations

import dataclasses
from pathlib import Path

import pytest

import trace_reduce as tr
import trace_scopes as ts
from run import load_module

BENCH = Path(__file__).resolve().parent
PROBE = BENCH / "testdata" / "tpu_probe.xplane.pb"
ENGINE = BENCH / "testdata" / "tpu_engine.xplane.pb"
PROGRAMS = {"decode": r"^jit_decode\b", "prefill": r"^jit_prefill\b"}
DEV = "/device:TPU:0"


def _host(name, a, b):
    return tr.Ev("/host:CPU", "python3", name, a, b)


def _dev(line, name, a, b):
    return tr.Ev(DEV, line, name, a, b)


def _op(name, a, b, tf_op, line=tr.OPS_LINE):
    return ts.Op(DEV, line, name, a, b, tf_op)


# -- scope names -------------------------------------------------------------

@pytest.mark.parametrize("tf_op,scope", [
    ("jit(decode)/jit(main)/while/body/closed_call/attention/circulant/"
     "dot_general:", "circulant"),
    ("jit(decode)/attention/bhqk,bkhd->bqhd/dot_general:", "attention"),
    ("jit(decode)/kv_move/scatter", "kv_move"),
    ("jit(decode)/head/...d,vd->...v/dot_general:", "head"),
    ("jit(train)/transpose(jvp(circulant))/fft", "circulant"),
    ("jit(decode)/while", "other"),
    ("jit(decode)/attention_core/dot_general", "other"),
    ("", "other"),
])
def test_scope_of_takes_the_innermost_known_segment(tf_op, scope):
    assert ts.scope_of(tf_op) == scope


def test_innermost_labels_by_the_shortest_covering_item():
    items = [(0.0, 10.0, "outer"), (2.0, 6.0, "mid"), (3.0, 4.0, "in"),
             (8.0, 9.0, "late")]
    assert ts.innermost(items, -1.0, 11.0) == [
        (-1.0, 0.0, None), (0.0, 2.0, "outer"), (2.0, 3.0, "mid"),
        (3.0, 4.0, "in"), (4.0, 6.0, "mid"), (6.0, 8.0, "outer"),
        (8.0, 9.0, "late"), (9.0, 10.0, "outer"), (10.0, 11.0, None)]


# -- synthetic traces --------------------------------------------------------

def _engine_trace():
    """One window [0, 10]: two engine steps, the device busy in each
    step's decode module and in one prefill module."""
    ev = [_host("bench.window", 0.0, 10.0)]
    for s0 in (0.5, 5.0):
        ev += [_host("bench.step", s0, s0 + 4.0),
               _host("serve.step", s0 + 0.1, s0 + 3.9),
               _host("serve.admit", s0 + 0.1, s0 + 0.3),
               _host("serve.decode.prep", s0 + 0.3, s0 + 0.4),
               _host("serve.decode.launch", s0 + 0.4, s0 + 0.6),
               _host("serve.decode.fetch", s0 + 0.6, s0 + 3.0),
               _host("serve.decode.sample", s0 + 3.0, s0 + 3.8)]
        ev += [_host("bench.poll", s0 + 4.0, s0 + 4.2)]
    # device: decode module [s0+0.6, s0+2.6] in both steps; one prefill
    # module [9.5, 9.9] under nothing but the window
    ev += [_dev(tr.MODULES_LINE, "jit_decode(1)", 1.1, 3.1),
           _dev(tr.MODULES_LINE, "jit_decode(1)", 5.6, 7.6),
           _dev(tr.MODULES_LINE, "jit_prefill(2)", 9.5, 9.9)]
    ops = []
    for m0 in (1.1, 5.6):
        ops += [
            _op("%while.1", m0, m0 + 1.6, "jit(decode)/while"),
            _op("%fusion.1", m0 + 0.1, m0 + 0.5,
                "jit(decode)/while/body/attention/circulant/dot_general:"),
            _op("%fusion.2", m0 + 0.5, m0 + 0.9,
                "jit(decode)/while/body/attention/dot_general:"),
            _op("%gather.3", m0 + 1.6, m0 + 1.8,
                "jit(decode)/kv_move/gather"),
            _op("%fusion.4", m0 + 1.8, m0 + 1.9,
                "jit(decode)/head/dot_general"),
            _op("%copy.5", m0 + 1.9, m0 + 2.0, ""),
        ]
    ops.append(_op("%fusion.9", 9.5, 9.9,
                   "jit(prefill)/attention/circulant/dot_general:"))
    mods = [ts.Op(e.plane, e.line, e.name, e.start, e.end, "")
            for e in ev if e.line == tr.MODULES_LINE]
    ev += [tr.Ev(o.plane, o.line, o.name, o.start, o.end) for o in ops]
    return ev, ops + mods


def test_idle_by_span_splits_the_idle_time_to_the_innermost_span():
    ev, ops = _engine_trace()
    r = ts.reduce_scopes(ev, ops, PROGRAMS["decode"])
    old = tr.reduce_events(ev, PROGRAMS)
    idle = r["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        old["window_s"] - old["busy_s"], abs=1e-12)
    # step 1 (0.5-4.5): busy 1.1-3.1, inside serve.decode.fetch (1.1-3.5)
    assert idle["serve.decode.launch"] == pytest.approx(2 * 0.2)
    assert idle["serve.decode.fetch"] == pytest.approx(2 * 0.4)
    assert idle["serve.decode.sample"] == pytest.approx(2 * 0.8)
    assert idle["serve.admit"] == pytest.approx(2 * 0.2)
    assert idle["serve.step"] == pytest.approx(2 * 0.1)
    assert idle["bench.step"] == pytest.approx(2 * 0.2)
    assert idle["bench.poll"] == pytest.approx(2 * 0.2)
    # outside every bench.*/serve.* span but the window
    assert idle["none"] == pytest.approx(0.5 + 0.3 + (9.5 - 9.2) + 0.1)
    # the longest gap, 3.1-5.6, named at its middle
    assert r["span_gaps"][0] == ["serve.step", pytest.approx(2.5)]
    assert r["serve_spans"]["serve.step"]["count"] == 2
    assert r["serve_spans"]["serve.decode.fetch"]["total_s"] == (
        pytest.approx(4.8))


def test_device_scopes_sum_to_the_decode_program_time():
    ev, ops = _engine_trace()
    r = ts.reduce_scopes(ev, ops, PROGRAMS["decode"])
    old = tr.reduce_events(ev, PROGRAMS)
    sc = r["device_scopes"]
    assert sum(sc.values()) == pytest.approx(
        old["programs"]["decode"]["device_s"], abs=1e-12)
    # a while nests its body: its self time is what no body op covers
    assert sc["circulant"] == pytest.approx(2 * 0.4)
    assert sc["attention"] == pytest.approx(2 * 0.4)
    assert sc["kv_move"] == pytest.approx(2 * 0.2)
    assert sc["head"] == pytest.approx(2 * 0.1)
    assert sc["other"] == pytest.approx(2 * (0.1 + 0.7 + 0.1))
    assert [n for n, _ in r["scope_ops"]["other"]] == ["%while.1", "%copy.5"]
    # the prefill module's op is not counted
    assert "%fusion.9" not in str(r["scope_ops"])


def test_a_trace_without_engine_spans_or_scopes():
    """The parent's program: no serve.* span, no scope in any tf_op."""
    ev, ops = _engine_trace()
    ev = [e for e in ev if not e.name.startswith("serve.")]
    ops = [dataclasses.replace(o, tf_op="jit(decode)/dot_general")
           for o in ops]
    r = ts.reduce_scopes(ev, ops, PROGRAMS["decode"])
    assert set(r["idle_by_span"]) <= {"bench.step", "bench.poll", "none"}
    assert r["serve_spans"] == {}
    assert all(v == 0 for k, v in r["device_scopes"].items() if k != "other")
    assert r["device_scopes"]["other"] > 0


def test_no_window_span_gives_none():
    ev, ops = _engine_trace()
    ev = [e for e in ev if e.name != tr.WINDOW_SPAN]
    assert ts.reduce_scopes(ev, ops, PROGRAMS["decode"]) is None


# -- recorded TPU traces -----------------------------------------------------

def test_tf_op_reader_on_a_recorded_tpu_trace():
    ops = ts.read_device_ops(str(PROBE))
    assert any(o.tf_op.startswith("jit(decode)/dot_general") for o in ops)
    assert any(o.tf_op.startswith("jit(prefill)/dot_general") for o in ops)
    # the same events, on the same clock, as jax.profiler.ProfileData's
    ev = [e for e in tr.load(str(PROBE)) if tr.DEVICE_PLANE.match(e.plane)
          and e.line in (tr.OPS_LINE, tr.MODULES_LINE)]
    key = lambda e: (e.line, e.start, e.name)  # noqa: E731
    assert len(ev) == len(ops)
    for e, o in zip(sorted(ev, key=key), sorted(ops, key=key)):
        assert (e.line, e.name) == (o.line, o.name)
        assert o.start == pytest.approx(e.start, abs=1e-8)
        assert o.end == pytest.approx(e.end, abs=1e-8)


def test_old_keys_unchanged_on_a_recorded_tpu_trace():
    old = tr.reduce_events(tr.load(str(PROBE)), PROGRAMS)
    new = ts.reduce(str(PROBE), PROGRAMS)
    assert {k: new[k] for k in old} == old


def test_scopes_in_a_recorded_engine_trace():
    """A two-layer engine's decode and prefill recorded on a TPU v5e
    (``record_trace.py``): each scope is in some op's ``tf_op``, and the
    engine's spans and the sums hold there."""
    ops = ts.read_device_ops(str(ENGINE))
    scoped = {ts.scope_of(o.tf_op) for o in ops
              if o.tf_op.startswith("jit(decode)")}
    assert scoped >= set(ts.SCOPES)
    r = ts.reduce(str(ENGINE), PROGRAMS)
    assert {"serve.step", "serve.decode.launch", "serve.decode.fetch",
            "serve.decode.sample"} <= set(r["serve_spans"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    dec = r["programs"]["decode"]["device_s"]
    assert sum(r["device_scopes"].values()) == pytest.approx(dec, rel=0.01)
    assert all(r["device_scopes"][s] > 0 for s in ts.SCOPES)


# -- readers -----------------------------------------------------------------

READERS = {
    "decode_launch_ms": ("idle_by_span", "serve.decode.launch"),
    "logits_copy_ms": ("idle_by_span", "serve.decode.fetch"),
    "host_sample_ms": ("idle_by_span", "serve.decode.sample"),
    "circulant_ms": ("device_scopes", "circulant"),
    "attention_ms": ("device_scopes", "attention"),
    "kv_move_ms": ("device_scopes", "kv_move"),
    "head_ms": ("device_scopes", "head"),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_scope_readers_per_decode_launch(name):
    key, sub = READERS[name]
    read = load_module(BENCH / "metrics" / f"{name}.py").read
    progs = {"decode": {"device_s": 1.0, "launches": 40.0}}
    res = {"trace": {"programs": progs, key: {sub: 0.2}}}
    assert read(res, name + ".itl") == pytest.approx(5.0)
    # a trace of a program without spans or scopes: nothing to read
    assert read({"trace": {"programs": progs}}, name) is None
    assert read({"trace": None}, name) is None
    progs["decode"]["launches"] = 0
    assert read(res, name) is None
