"""``BENCHMARK.json`` and the files it names: every cell, configuration,
traffic mix, limit and metric is found by name."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

import run

BENCH = Path(__file__).resolve().parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_names_units_sources():
    names = [m["name"] for m in METRICS] + CELLS + [
        c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = run.load_cell(cell)
    assert (BENCH / "entries" / f"{c['traffic']['entry']}.py").exists()
    assert c["traffic"]["cache_len"] <= c["config"]["max_position_embeddings"]
    e2e = [m["name"] for m in run.cell_metrics(SPEC, cell, False)]
    per_layer = run.cell_metrics(SPEC, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and per_layer
    for m in run.cell_metrics(SPEC, cell, False) + per_layer:
        assert run.reader_path(m["name"]).exists(), m["name"]


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configs(conf):
    f = BENCH.parent / conf["file"]
    cj = json.loads(f.read_text())
    assert conf["file"].startswith("bench/") and f.exists()
    assert set(conf["reduced"]) <= set(cj)
    width = re.compile(r"(hidden|intermediate|latent|state|proj|head)|"
                       r"(_dim|_rank)$|per_tok")
    for k in conf["reduced"]:
        assert not width.search(k), k
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])
