"""BENCHMARK.json against the shape the harness and its checker need:
names, units, keys, lengths, that every named file exists, and that
every per-layer metric's cells report the metric it moves."""
from __future__ import annotations

import json
import re

import pytest

from chipbench.lib import check, spec
from chipbench.lib.spec import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = spec.load_benchmark()
METRIC_KEYS = {"name", "unit", "better", "source"}


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    assert B["paths"] == ["chipbench"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    cells = len(B["workloads"])
    # a full check with 24 cells fits the driver's 43200 s
    assert 2 + 14 * 24 * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert cells <= 24


def test_configs():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _text_ok(c["why"])
        assert c["file"].startswith("chipbench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert (BENCH_DIR / "configs" / f"{cfg['reference']}.py").exists()
        assert c["name"] in {w["config"] for w in B["workloads"]}


@pytest.mark.parametrize("name", [c["name"] for c in B["configs"]])
def test_config_has_a_program_and_a_mesh_for_its_chips(name):
    """The program side of the file's model_type exists and has both
    functions the harness calls; a configuration that a cell runs on several
    chips states its serve.mesh, and a serve.mesh is as many chips as
    every cell of the configuration asks for."""
    config = spec.load_config(name, B)
    prog = spec.program_module(config)
    assert callable(prog.model_config) and callable(prog.params)
    mesh = config["serve"].get("mesh")
    for w in B["workloads"]:
        if w["config"] != name:
            continue
        if mesh is None:
            assert w["chips"] == 1, w["name"]
        else:
            assert set(mesh) == {"data", "model"}
            assert mesh["data"] * mesh["model"] == w["chips"], w["name"]


def test_workloads_name_their_files():
    pairs = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _text_ok(w["why"]) and w["chips"] in (1, 4)
        assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").exists()
        named = json.loads(
            (BENCH_DIR / "cells" / f"{w['name']}.json").read_text())
        assert named and set(named) <= set(check.NUMBERS)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(B["workloads"])


def test_metrics():
    names = set()
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert _text_ok(m["layer"]) and m["moves"] in e2e
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
        for cell in m.get("workloads", [w["name"] for w in B["workloads"]]):
            reported = {x["name"] for x in spec.cell_metrics(
                cell, "end_to_end", B)}
            assert m["moves"] in reported, (m["name"], cell)


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_cell_reports_enough(cell):
    e2e = {m["name"] for m in spec.cell_metrics(cell, "end_to_end", B)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.cell_metrics(cell, "per_layer", B)
