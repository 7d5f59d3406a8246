#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's, and its control's.

    python3 bench/control.py --workload qwen3-0.6b.chat \
        --seeds 11,12,13 --seconds 30

Runs the cell as a benchmark run does (set-up, a window at the cell's own
load, the sample of finished requests), once per seed in one process, and
prints per seed the readings of the program's served tokens (``served``)
and of the control (``control``): at the same positions of the same
prompts and served tokens, how far the float32 reference's logit for the
int8 control's first choice (``reference.py``, ``fmt="int8"``) lies below
its best. ``control_correct`` is the harness's own verdict (``judge``) on
the control's numbers against the cell's limits, which has to be false;
``correct`` is the program's. A limit in ``bench/checks/<cell>.json``
lies between the largest program reading and the smallest control
reading. Not part of a benchmark run: it is run on the chip when a limit
is set.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    _, drv = run.prepare(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = drv.run(cell, seed=seed, seconds=args.seconds, trace=False,
                      t_process=time.perf_counter(), control="int8")
        print(json.dumps({
            "seed": seed, "control_correct": res["control_correct"],
            "correct": res["correct"],
            "control_mean_gap": res["control_check"]["mean_gap"],
            "checked_tokens": res["check"]["checked_tokens"]["value"],
            "attempted": res["attempted"], "failed": res["failed"],
            **res["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
