#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload qwen3-0.6b.chat --seed 7 --seconds 30 \
        --trace 0

Everything a cell is made of is found by name, from ``BENCHMARK.json`` at
the root of the checkout:

* the cell (``workloads``) names a configuration and a traffic mix;
* ``bench/configs/<config>.json``: the model as it is run;
* ``bench/traffic/<mix>.json``: the mix's parameters, read by
  ``loadgen.py``; its ``entry`` names ``bench/entries/<entry>.py``, which
  sets up the system, measures the window and checks the outputs;
* ``bench/checks/<cell>.json``: the limits ``correct`` is judged by;
* ``bench/metrics/<metric>.py`` (or ``<base>.py`` for ``<base>.<suffix>``):
  one reader per metric, given the run's records. A reader that finds
  nothing to read returns None and the metric is left out.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of a few seconds of the window.
The last line of standard output is one JSON object; the numbers the
check compared, each beside its limit, are the last lines of standard
error and the last key of that object. There is no fallback: without a
TPU, or with fewer chips than the cell asks for, the run exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(name: str) -> Path:
    """``metrics/<name>.py``, else the reader of the name's base."""
    full = BENCH / "metrics" / f"{name}.py"
    return full if full.exists() else BENCH / "metrics" / (
        name.split(".")[0] + ".py")


def cell_metrics(bench: dict, cell: str, trace: bool):
    """The metrics this cell reports in this kind of run."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in e2e]


def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; choices: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {
        "bench": bench,
        "name": name,
        "chips": int(w["chips"]),
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "checks" / f"{name}.json").read_text()),
    }


def device_info(chips: int) -> dict:
    """JAX's devices, refused unless they are at least ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise RuntimeError(f"this cell needs {chips} TPU chip(s); JAX "
                           f"reports {info}")
    return info


def prepare(cell: dict):
    """Put the program and the benchmark on the path, check the devices
    (``RuntimeError`` without enough TPUs) and load the cell's entry."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    device = device_info(cell["chips"])
    return device, load_module(
        BENCH / "entries" / f"{cell['traffic']['entry']}.py")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        device, entry = prepare(cell)
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    res = entry.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_process=T_PROCESS)
    res["device"] = device

    metrics = {}
    for m in cell_metrics(cell["bench"], cell["name"], bool(args.trace)):
        v = load_module(reader_path(m["name"])).read(res, m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": dev}
    tr = res.get("trace")
    if args.trace and tr is not None:
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["counters"] = res["counters"]
    out["setup_parts"] = res["setup_parts"]
    out["readings"] = res["readings"]
    out["check"] = res["check"]
    print(f"counters {json.dumps(res['counters'])}", file=sys.stderr)
    for k, c in res["check"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
