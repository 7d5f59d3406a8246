"""Model FLOPs the tokens served in the traced window required
(``flops.py``), over the device's busy seconds times its bf16 peak
(``peaks.json``). A first token costs its prompt's prefill; token ``i``
after it one decode step attending ``prompt + i`` positions."""

import json
from pathlib import Path

import flops


def peak_flops(kind: str) -> float:
    peaks = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    if kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return float(peaks["devices"][kind]["bf16_flops_per_s"])


def read(res, name):
    tr = res.get("trace")
    a, b = res.get("trace_t", (None, None))
    if not tr or not tr["busy_s"] or a is None or b is None:
        return None
    cfg, total = res["config"], 0
    for r in res["records"]:
        for i, t in enumerate(r.tok_times):
            if a <= t <= b:
                total += (flops.prefill_flops(cfg, r.prompt_len) if i == 0
                          else flops.decode_flops(cfg, r.prompt_len + i))
    peak = peak_flops(res["device"]["kind"]) * tr["devices"]
    return 100.0 * total / (tr["busy_s"] * peak)
