"""``BENCHMARK.json`` names only what exists: every cell's configuration,
traffic mix and limits file, and a reader for every metric."""
from __future__ import annotations

import os
import re

import pytest

import common

SPEC = common.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_are_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = common.cell(SPEC, cell)
    assert c["traffic"]["kind"] in ("open_loop", "offline")
    assert os.path.exists(os.path.join(common.BENCH, "checks", cell + ".json"))
    names = {m["name"] for m in c["metrics"]["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["metrics"]["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(common.reader(metric))


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", m["workloads"]))
