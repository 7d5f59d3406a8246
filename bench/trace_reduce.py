"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced window is the benchmark's own host span ``bench.window``; every
number is clipped to it:

* ``busy_s`` — the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices;
* ``programs`` — per jitted program, matched by a pattern on the ``XLA
  Modules`` line (``jit_decode(...)``), its device seconds and launches;
* ``device_ops`` — the operations that took the most device time, by
  their HLO name;
* ``idle_gaps`` — the longest stretches with no operation on the device,
  each named by the innermost ``bench.*`` host span that covers it (what
  the benchmark's host thread was doing then).

Host and device events share the trace's clock.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Ev", "load", "find_xplane", "merge", "reduce_events",
           "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
TOP = 10


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start: float        # seconds
    end: float


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def load(path: str) -> List[Ev]:
    """Every event of the trace file, with times in seconds."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append(Ev(plane.name, line.name, e.name, s,
                              s + e.duration_ns * 1e-9))
    return out


def merge(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _host_name(spans: Sequence[Ev], t: float) -> str:
    inner = [s for s in spans if s.start <= t <= s.end]
    if not inner:
        return "none"
    return min(inner, key=lambda s: s.end - s.start).name


def reduce_events(events: Sequence[Ev],
                  programs: Dict[str, str]) -> Optional[dict]:
    """The numbers of the traced window; None when the trace has no
    window span or no device plane. ``programs`` maps a name to a regular
    expression searched in module names."""
    wins = [e for e in events if e.name == WINDOW_SPAN]
    if not wins:
        return None
    lo, hi = wins[0].start, wins[0].end
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if not planes:
        return None
    host = [e for e in events if e.name.startswith("bench.")
            and not DEVICE_PLANE.match(e.plane)]
    busy_total = 0.0
    op_time: Dict[str, float] = collections.defaultdict(float)
    prog = {n: {"device_s": 0.0, "launches": 0} for n in programs}
    pats = {n: re.compile(p) for n, p in programs.items()}
    gaps: List[Tuple[float, float]] = []
    for pl in planes:
        ops = [e for e in events if e.plane == pl and e.line == OPS_LINE]
        mods = [e for e in events if e.plane == pl and e.line == MODULES_LINE]
        busy = _clip(merge((e.start, e.end) for e in (ops or mods)), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for e in ops:
            a, b = max(e.start, lo), min(e.end, hi)
            if b > a:
                op_time[e.name.split(" = ")[0]] += b - a
        for e in mods:
            a, b = max(e.start, lo), min(e.end, hi)
            if b <= a:
                continue
            for n, p in pats.items():
                if p.search(e.name):
                    prog[n]["device_s"] += b - a
                    prog[n]["launches"] += 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    n = len(planes)
    spans: Dict[str, Dict[str, float]] = {}
    for s in host:
        if s.name == WINDOW_SPAN or not (lo <= s.start and s.end <= hi):
            continue
        d = spans.setdefault(s.name, {"count": 0, "total_s": 0.0})
        d["count"] += 1
        d["total_s"] += s.end - s.start
    gaps.sort(key=lambda g: -g[0])
    named = [[_host_name(host, mid), g] for g, mid in gaps[:TOP]]
    return {
        "window_s": hi - lo,
        "busy_s": busy_total / n,
        "devices": n,
        "programs": {k: {"device_s": v["device_s"] / n,
                         "launches": v["launches"] / n}
                     for k, v in prog.items()},
        "device_ops": [[k, v / n] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": named,
        "host_spans": spans,
    }
