#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate served without a growing
backlog. Run once, on the chip, when a cell is defined; the cell then runs
at a fixed rate written into its mix. Not part of a benchmark run.

    python3 bench/sweep.py --workload qwen3-0.6b.chat --seed 3 \
        --rates 0.5,1,2,4 --seconds 20

One process, one engine: set-up once, then one window per rate, each
ended by cancelling what is still open. For each rate it prints one JSON
line: requests due and answered, the backlog (requests due but not yet
answered) at half time and at the close, the TTFT and inter-token tails,
and output tokens per second.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def backlog(records, t: float) -> int:
    return sum(1 for r in records if r.due <= t
               and not (r.tok_times and r.tok_times[0] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    _, drv = run.prepare(cell)
    import clientstats as cs
    import loadgen
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cj, mix = cell["config"], cell["traffic"]
    engine = drv.build_engine(cj, mix, args.seed)
    drv.warm_up(engine)
    print(f"setup_s {time.perf_counter() - T_PROCESS:.1f}", flush=True)
    for rate in (float(x) for x in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        reqs = loadgen.make_requests(m, args.seed, args.seconds,
                                     cj["vocab_size"])
        recs, t0, t1 = drv.serve_window(engine, m, reqs, args.seconds,
                                        drv.Tracer(False))
        ttft = cs.ttfts(recs, t0, t1)
        out = {
            "rate_per_s": rate, "due": len(ttft),
            "answered": sum(1 for r in recs if r.tok_times
                            and r.tok_times[0] <= t1),
            "backlog_half": backlog(recs, (t0 + t1) / 2),
            "backlog_end": backlog(recs, t1),
            "ttft_p50_ms": 1e3 * (cs.percentile(ttft, 50) or 0),
            "ttft_p95_ms": 1e3 * (cs.percentile(ttft, 95) or 0),
            "itl_p99_ms": 1e3 * (cs.percentile(
                cs.inter_token_gaps(recs, t0, t1), 99) or 0),
            "out_tok_s": cs.tokens_in_window(recs, t0, t1) / (t1 - t0),
        }
        print(json.dumps(out), flush=True)
        for r in recs:
            if r.status is None:
                engine.cancel(r.rid)
        while engine.step():
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
