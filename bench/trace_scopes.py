"""Engine spans and model-step scopes in a profiler trace (``.xplane.pb``).

``trace_reduce`` names the device's idle time by the benchmark's own host
spans and its busy time by HLO name. This module reads what the program
marks itself, in the same window (the host span ``bench.window``):

* ``idle_by_span`` — the device-idle seconds of the window, split instant
  by instant to the innermost ``bench.*`` / ``serve.*`` host span that
  covers it (``ServeEngine`` records ``serve.step``, ``serve.admit``,
  ``serve.prefill.{launch,fetch,sample}`` and
  ``serve.decode.{prep,launch,fetch,sample}``); ``none`` where the host was
  in none of them;
* ``span_gaps`` — the longest device-idle stretches, each named by the
  innermost such span at its middle;
* ``serve_spans`` — count and total seconds of each ``serve.*`` span that
  lies inside the window;
* ``device_scopes`` — the *self* device time (an op's interval less the
  ops nested in it on the same line: a ``while`` nests its body) of the
  ops that ran inside a decode module event, each put down to the
  innermost of the model step's ``jax.named_scope`` names (``SCOPES``) in
  its ``tf_op``, else to ``other``; ``scope_ops`` lists the ops that took
  most of each.

An op's ``tf_op`` is the JAX op path (``jit(decode)/.../attention/
dot_general``) that the xplane keeps in its event metadata, which
``jax.profiler.ProfileData`` does not expose; ``read_device_ops`` reads it
with a small protobuf decoder. Device seconds are averaged over devices,
as in ``trace_reduce``.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import trace_reduce

__all__ = ["SCOPES", "Op", "read_device_ops", "scope_of", "innermost",
           "reduce_scopes", "reduce", "per_decode_ms"]

SCOPES = ("circulant", "attention", "kv_move", "head")
SPAN_PREFIXES = ("bench.", "serve.")
TOP = 10

# wire types of the protobuf encoding
_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


@dataclasses.dataclass(frozen=True)
class Op:
    plane: str
    line: str
    name: str
    start: float        # seconds, on the clock of ``trace_reduce.load``
    end: float
    tf_op: str


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` slice for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == _VARINT:
            v, i = _varint(buf, i)
        elif wt == _LEN:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == _I64:
            v, i = buf[i:i + 8], i + 8
        elif wt == _I32:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield key >> 3, v


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[tuple]]:
    key, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _tf_ops(buf: bytes, ev_meta, stat_meta) -> Dict[int, Tuple[str, str]]:
    """Event metadata id -> (name, tf_op) of one plane."""
    stat_names = {}
    for span in stat_meta:
        k, v = _map_entry(buf, span)
        for f, x in _fields(buf, *v) if v else ():
            if f == 2:
                stat_names[k] = _str(buf, x)
    tf_id = {i for i, n in stat_names.items() if n == "tf_op"}
    out = {}
    for span in ev_meta:
        k, v = _map_entry(buf, span)
        name, tf_op = "", ""
        for f, x in _fields(buf, *v) if v else ():
            if f == 2:
                name = _str(buf, x)
            elif f == 5:
                sid, sval = None, ""
                for g, y in _fields(buf, *x):
                    if g == 1:
                        sid = y
                    elif g == 5:
                        sval = _str(buf, y)
                    elif g == 7:
                        sval = stat_names.get(y, "")
                if sid in tf_id:
                    tf_op = sval
        out[k] = (name, tf_op)
    return out


def read_device_ops(path: str) -> List[Op]:
    """The events of the ops and modules lines of every device plane, each
    with the ``tf_op`` of its event metadata ("" where it has none)."""
    with open(path, "rb") as f:
        buf = f.read()
    out: List[Op] = []
    for f, pspan in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, line_spans, ev_meta, stat_meta = "", [], [], []
        for g, v in _fields(buf, *pspan):
            if g == 2:
                name = _str(buf, v)
            elif g == 3:
                line_spans.append(v)
            elif g == 4:
                ev_meta.append(v)
            elif g == 5:
                stat_meta.append(v)
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        meta = None
        for lspan in line_spans:
            lname, ts_ns, evs = "", 0, []
            for g, v in _fields(buf, *lspan):
                if g == 2:
                    lname = _str(buf, v)
                elif g == 3:
                    ts_ns = v
                elif g == 4:
                    evs.append(v)
            if lname not in (trace_reduce.OPS_LINE,
                             trace_reduce.MODULES_LINE):
                continue
            if meta is None:
                meta = _tf_ops(buf, ev_meta, stat_meta)
            for espan in evs:
                mid = off = dur = 0
                for g, v in _fields(buf, *espan):
                    if g == 1:
                        mid = v
                    elif g == 2:
                        off = v
                    elif g == 3:
                        dur = v
                ename, tf_op = meta.get(mid, ("", ""))
                s = ts_ns * 1e-9 + off * 1e-12
                out.append(Op(name, lname, ename, s, s + dur * 1e-12, tf_op))
    return out


_WRAP = re.compile(r"^(?:[\w-]+\()+(.*?)\)+$")


def scope_of(tf_op: str) -> str:
    """The innermost ``SCOPES`` name among the path segments of a
    ``tf_op`` (a transform wraps a segment, as in ``jvp(circulant)``),
    else ``other``."""
    for seg in reversed(tf_op.split("/")):
        m = _WRAP.match(seg)
        name = m.group(1) if m else seg
        if name in SCOPES:
            return name
    return "other"


def innermost(items: Sequence[Tuple[float, float, object]],
              lo: float, hi: float) -> List[Tuple[float, float, object]]:
    """Split ``[lo, hi]`` into stretches, each labelled by the shortest
    item ``(start, end, label)`` covering it (the innermost, for items
    that nest), or None where none does."""
    pts = sorted({lo, hi} | {t for a, b, _ in items for t in (a, b)
                             if lo < t < hi})
    order = sorted(range(len(items)), key=lambda k: items[k][0])
    heap: List[Tuple[float, int]] = []
    out: List[Tuple[float, float, object]] = []
    j = 0
    for a, b in zip(pts, pts[1:]):
        while j < len(order) and items[order[j]][0] <= a:
            k = order[j]
            heapq.heappush(heap, (items[k][1] - items[k][0], k))
            j += 1
        while heap and items[heap[0][1]][1] <= a:
            heapq.heappop(heap)
        lab = items[heap[0][1]][2] if heap else None
        if out and out[-1][2] is lab and out[-1][1] == a:
            out[-1] = (out[-1][0], b, lab)
        else:
            out.append((a, b, lab))
    return out


def _overlap(segs, ivs) -> Iterator[Tuple[float, float, object]]:
    """The parts of sorted disjoint intervals ``ivs`` under each labelled
    stretch of ``segs`` (sorted and disjoint as well)."""
    i = 0
    for a, b, lab in segs:
        while i < len(ivs) and ivs[i][1] <= a:
            i += 1
        k = i
        while k < len(ivs) and ivs[k][0] < b:
            x, y = max(a, ivs[k][0]), min(b, ivs[k][1])
            if y > x:
                yield x, y, lab
            k += 1


def reduce_scopes(events: Sequence[trace_reduce.Ev], ops: Sequence[Op],
                  decode: str) -> Optional[dict]:
    """The engine's and the model step's numbers of the traced window.
    ``events`` as ``trace_reduce.load`` gives them, ``ops`` as
    ``read_device_ops`` does, ``decode`` a regular expression searched in
    the names of the decode program's module events. None when the trace
    has no window span or no device plane."""
    wins = [e for e in events if e.name == trace_reduce.WINDOW_SPAN]
    planes = sorted({e.plane for e in events
                     if trace_reduce.DEVICE_PLANE.match(e.plane)})
    if not wins or not planes:
        return None
    lo, hi = wins[0].start, wins[0].end
    spans = [e for e in events if e.name.startswith(SPAN_PREFIXES)
             and e.name != trace_reduce.WINDOW_SPAN
             and not trace_reduce.DEVICE_PLANE.match(e.plane)]
    who = innermost([(s.start, s.end, s.name) for s in spans], lo, hi)
    pat = re.compile(decode)
    idle: Dict[str, float] = collections.defaultdict(float)
    scopes: Dict[str, float] = dict.fromkeys(SCOPES + ("other",), 0.0)
    per_op: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    gaps: List[Tuple[float, float]] = []
    for pl in planes:
        dev = [e for e in events if e.plane == pl and e.line in
               (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)]
        line = (trace_reduce.OPS_LINE
                if any(e.line == trace_reduce.OPS_LINE for e in dev)
                else trace_reduce.MODULES_LINE)
        busy = trace_reduce.merge(
            (max(e.start, lo), min(e.end, hi)) for e in dev
            if e.line == line and e.end > lo and e.start < hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        free = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps.extend(free)
        for a, b, lab in _overlap(who, free):
            idle[lab or "none"] += b - a
        mods = trace_reduce.merge(
            (max(o.start, lo), min(o.end, hi)) for o in ops
            if o.plane == pl and o.line == trace_reduce.MODULES_LINE
            and pat.search(o.name) and o.end > lo and o.start < hi)
        line_ops = [(o.start, o.end, o) for o in ops if o.plane == pl
                    and o.line == trace_reduce.OPS_LINE]
        if not mods or not line_ops:
            continue
        inner = innermost(line_ops, mods[0][0], mods[-1][1])
        for a, b, o in _overlap(inner, mods):
            if o is not None:
                sc = scope_of(o.tf_op)
                scopes[sc] += b - a
                per_op[sc][o.name.split(" = ")[0]] += b - a
    n = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    served = {}
    for s in spans:
        if s.name.startswith("serve.") and lo <= s.start and s.end <= hi:
            d = served.setdefault(s.name, {"count": 0, "total_s": 0.0})
            d["count"] += 1
            d["total_s"] += s.end - s.start
    return {
        "idle_by_span": {k: v / n for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
        "span_gaps": [[_label_at(who, (a + b) / 2), b - a]
                      for a, b in gaps[:TOP]],
        "serve_spans": served,
        "device_scopes": {k: v / n for k, v in scopes.items()},
        "scope_ops": {k: [[o, s / n] for o, s in sorted(
            v.items(), key=lambda kv: -kv[1])[:TOP]]
            for k, v in per_op.items()},
    }


def _label_at(segs, t: float) -> str:
    for a, b, lab in segs:
        if a <= t <= b:
            return lab or "none"
    return "none"


def reduce(path: str, programs: Dict[str, str]) -> Optional[dict]:
    """``trace_reduce.reduce_events`` of one trace file with the keys of
    ``reduce_scopes`` added (``programs["decode"]`` names the decode
    program)."""
    events = trace_reduce.load(path)
    out = trace_reduce.reduce_events(events, programs)
    if out is not None:
        out.update(reduce_scopes(events, read_device_ops(path),
                                 programs["decode"]))
    return out


def per_decode_ms(res: dict, value: Optional[float]) -> Optional[float]:
    """``value`` seconds per launch of the decode program in the trace,
    in ms; None without a trace, a value or a launch."""
    tr = res.get("trace")
    p = tr and tr["programs"].get("decode")
    if value is None or not p or not p["launches"]:
        return None
    return 1e3 * value / p["launches"]
