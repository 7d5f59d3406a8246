#!/usr/bin/env python3
"""Run one serving cell traced, and print the engine's and the model
step's breakdown of the traced stretch.

    python3 bench/trace_breakdown.py --workload qwen3-0.6b.chat --seed 7 \
        --seconds 50 [--keep DIR]

The run is ``bench/run.py --trace 1``'s: the same entry, window, trace and
check. The trace is also reduced by ``trace_scopes`` (device-idle time by
engine span, decode device time by model-step scope), and the readers of
``SCOPE_METRICS`` are applied beside the cell's own per-layer metrics.
Each full garbage collection runs in a host span ``bench.gc``, so that
a device gap it causes is named by it. ``--keep`` copies the trace file
into DIR. The last line of standard output is one JSON object; without a
TPU the run exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import run as bench  # noqa: E402

SCOPE_METRICS = ("decode_launch_ms", "logits_copy_ms", "host_sample_ms",
                 "circulant_ms", "attention_ms", "kv_move_ms", "head_ms")


def scoped_tracer(entry, keep):
    """The entry's tracer, reducing with ``trace_scopes`` and copying the
    trace file into ``keep`` when given."""
    import trace_reduce
    import trace_scopes

    class ScopedTracer(entry.Tracer):
        def reduce(self):
            if self.dir is None or self.state != 2:
                return super().reduce()
            try:
                path = trace_reduce.find_xplane(self.dir)
                if keep:
                    os.makedirs(keep, exist_ok=True)
                    shutil.copy(path, keep)
                return trace_scopes.reduce(path, entry.PROGRAMS)
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    return ScopedTracer


def span_full_collections() -> None:
    """Enter a host span ``bench.gc`` for each generation-2 collection."""
    from jax.profiler import TraceAnnotation

    live = []

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            live.append(TraceAnnotation("bench.gc"))
            live[-1].__enter__()
        elif live:
            live.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    try:
        device, entry = bench.prepare(cell)
    except RuntimeError as e:
        print(f"trace_breakdown: {e}", file=sys.stderr)
        return 2
    entry.Tracer = scoped_tracer(entry, args.keep)
    span_full_collections()
    res = entry.run(cell, seed=args.seed, seconds=args.seconds, trace=True,
                    t_process=T_PROCESS)
    res["device"] = device
    names = [m["name"] for m in
             bench.cell_metrics(cell["bench"], cell["name"], True)]
    metrics = {}
    for name in names + list(SCOPE_METRICS):
        v = bench.load_module(bench.reader_path(name)).read(res, name)
        if v is not None:
            metrics[name] = v
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "counters": res["counters"],
           "full_collections": gc.get_stats()[2]["collections"]}
    tr = res["trace"]
    if tr is not None:
        out["breakdown"] = {k: tr[k] for k in (
            "device_ops", "idle_gaps", "span_gaps", "idle_by_span",
            "device_scopes", "scope_ops", "host_spans", "serve_spans",
            "programs")}
        out["sums"] = {
            "window_s": tr["window_s"], "busy_s": tr["busy_s"],
            "idle_s": tr["window_s"] - tr["busy_s"],
            "idle_by_span_s": sum(tr["idle_by_span"].values()),
            "decode_device_s": tr["programs"]["decode"]["device_s"],
            "device_scopes_s": sum(tr["device_scopes"].values()),
        }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
