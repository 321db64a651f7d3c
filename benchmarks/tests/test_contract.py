"""BENCHMARK.json against the harness's files: every name resolves,
every per-layer metric's cells report the end-to-end metric it moves."""

import os
import re

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = harness.load_benchmark()


def test_names_and_files():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            harness.HERE, "runners", cfg["runner"] + ".py"))
        assert "guarantees" in cfg and "source" in cfg
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        harness.cell_of(BENCH, w["name"])
    for m in BENCH["per_layer"]:
        spec = harness.load_json(os.path.join(
            harness.HERE, "layer_metrics", m["name"] + ".json"))
        assert os.path.exists(os.path.join(
            harness.HERE, "readers", spec["reader"] + ".py"))


def test_each_layer_metric_moves_a_metric_its_cells_report():
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert "setup_s" in [n for n, ws in e2e.items() if cell in ws]
        assert len([n for n, ws in e2e.items() if cell in ws]) >= 2
        assert harness.metrics_for(BENCH, "per_layer", cell)


def test_four_chip_cells_are_at_most_half():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
