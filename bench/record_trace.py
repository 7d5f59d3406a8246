#!/usr/bin/env python3
"""Record a small profiler trace of the serving engine on a TPU, for the
tests of the scope reader, and time the engine's spans on this host.

    python3 bench/record_trace.py --out bench/testdata/tpu_engine.xplane.pb

A two-layer decoder (block-circulant k = 8, tied head) behind
``ServeEngine``: two requests are admitted, then decoded for a few steps
inside a ``bench.window`` span while the profiler records. ``--out``
gets the trace trimmed to what ``trace_scopes`` reads: the device planes'
ops and modules lines with each op's name and ``tf_op``, and the host
line that holds ``bench.window``. The last line of standard output is
one JSON object: the file's size, the ``serve.*`` span names it holds,
and the cost of one span (``jax.profiler.TraceAnnotation`` with two
arguments, entered and left) with the profiler off and while it records.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import trace_reduce
import trace_scopes as ts

ROOT = Path(__file__).resolve().parents[1]
STEPS = 3
SPAN_REPS = 100_000


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _len_field(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _trim_plane(buf: bytes, span, keep_line, stat_keep) -> bytes:
    """One XPlane with the lines ``keep_line(name, metadata ids of its
    events, event metadata names)`` accepts, the event metadata their
    events use (id, name and the stats ``stat_keep`` accepts) and every
    stat metadata entry."""
    head, lines, stat_meta = [], [], []
    ev_meta, names, stat_names = {}, {}, {}
    for g, v in ts._fields(buf, *span):
        if g == 1:
            head.append(_varint(g << 3) + _varint(v))
        elif g == 2:
            head.append(_len_field(2, buf[v[0]:v[1]]))
        elif g == 3:
            lines.append(v)
        elif g == 4:
            k, val = ts._map_entry(buf, v)
            ev_meta[k] = val
            names[k] = next((ts._str(buf, x) for h, x in
                             ts._fields(buf, *val) if h == 2), "")
        elif g == 5:
            stat_meta.append(_len_field(5, buf[v[0]:v[1]]))
            k, val = ts._map_entry(buf, v)
            stat_names.update((k, ts._str(buf, x))
                              for h, x in ts._fields(buf, *val) if h == 2)
    kept, used = [], set()
    for v in lines:
        lname, ids = "", set()
        for h, x in ts._fields(buf, *v):
            if h == 2:
                lname = ts._str(buf, x)
            elif h == 4:
                ids.update(y for q, y in ts._fields(buf, *x) if q == 1)
        if keep_line(lname, ids, names):
            kept.append(_len_field(3, buf[v[0]:v[1]]))
            used |= ids
    metas = []
    for k in sorted(used & set(ev_meta)):
        body = _varint(1 << 3) + _varint(k)
        for h, x in ts._fields(buf, *ev_meta[k]):
            if h == 2:
                body += _len_field(2, buf[x[0]:x[1]])
            elif h == 5:
                sid = next(y for q, y in ts._fields(buf, *x) if q == 1)
                if stat_keep(stat_names.get(sid, "")):
                    body += _len_field(5, buf[x[0]:x[1]])
        entry = _varint(1 << 3) + _varint(k) + _len_field(2, body)
        metas.append(_len_field(4, entry))
    return b"".join(head + kept + metas + stat_meta)


def trim(buf: bytes) -> bytes:
    """The trace with the device planes' ops and modules lines (ops keep
    their ``tf_op``) and the host line that holds ``bench.window``."""
    def device_line(name, ids, names):
        return name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)

    def window_line(name, ids, names):
        return any(names.get(i) == trace_reduce.WINDOW_SPAN for i in ids)

    out = []
    for f, span in ts._fields(buf, 0, len(buf)):
        name = next((ts._str(buf, v) for g, v in ts._fields(buf, *span)
                     if g == 2), "") if f == 1 else ""
        if trace_reduce.DEVICE_PLANE.match(name):
            plane = _trim_plane(buf, span, device_line,
                                lambda stat: stat == "tf_op")
        elif name == "/host:CPU":
            plane = _trim_plane(buf, span, window_line, lambda stat: True)
        else:
            continue
        out.append(_len_field(1, plane))
    return b"".join(out)


def span_cost_us(reps: int) -> float:
    from jax.profiler import TraceAnnotation

    t = time.perf_counter()
    for i in range(reps):
        with TraceAnnotation("serve.decode.launch", step=i, rows=8):
            pass
    return (time.perf_counter() - t) / reps * 1e6


def toy_engine():
    from repro.configs.base import ModelConfig, SWMConfig
    from repro.configs.registry import get_smoke
    from repro.launch.specs import build_model
    from repro.nn.module import init_params
    from repro.serve.engine import ServeEngine

    cfg: ModelConfig = dataclasses.replace(
        get_smoke("qwen3-0.6b"), n_layers=2, tie_embeddings=True,
        swm=SWMConfig(block_size=8, impl="paper"))
    model = build_model(cfg)
    params = init_params(model.specs(), 0)
    return ServeEngine(model, cfg, params, batch=2, cache_len=64,
                       prompt_buckets=(16,))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.serve.engine import Request

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    eng = toy_engine()
    eng.prewarm()
    eng.generate([Request(np.arange(1, 9, dtype=np.int32), max_new=3)])
    reqs = [Request(np.arange(1, 1 + n, dtype=np.int32), max_new=STEPS + 2)
            for n in (5, 11)]
    for r in reqs:
        eng.submit(r)
    eng.step()                  # admission and prefill, left untraced
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("bench.window"):
            for _ in range(STEPS):
                with TraceAnnotation("bench.step"):
                    eng.step()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        Path(args.out).write_bytes(trim(Path(path).read_bytes()))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    spans = sorted({e.name for p in ProfileData.from_file(args.out).planes
                    for ln in p.lines for e in ln.events
                    if e.name.startswith("serve.")})

    off = span_cost_us(SPAN_REPS)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        jax.profiler.start_trace(tmp)
        on = span_cost_us(SPAN_REPS)
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "serve_spans": spans, "span_us_off": off,
                      "span_us_on": on}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
