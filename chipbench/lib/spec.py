"""Find a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

    chipbench/configs/<config>.json     sizes, as run, naming its
                                        reference; a serve.mesh
                                        {"data", "model"} for cells on
                                        several chips
    chipbench/configs/<reference>.py    its plain float32 reference:
        score(config, seed, rows, control=) -> per-position logits;
        _layer, _head, ROWS_PER_BLOCK, LEN_UNIT (compiled by
        tests/test_compile_v5e.py)
    chipbench/programs/<model_type>.py  the program side of it:
        model_config(config) -> the program's ModelConfig, checked
        against the file;
        params(seed, config, mesh) -> the served parameters, made on
        the device: placed on `mesh`, or on one device when it is None
    chipbench/traffic/<traffic>.json    parameters of the generator
    chipbench/cells/<cell>.json         the cell's limits of `correct`
    chipbench/metrics/<metric>.py       reader: read(run) -> value|None

so a later cell, mix, metric or model is added by adding files and
entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def load_benchmark() -> dict:
    return json.loads(BENCHMARK.read_text())


def cell(name: str, bench: dict | None = None) -> dict:
    bench = bench or load_benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in {BENCHMARK.name}")


def config_entry(name: str, bench: dict | None = None) -> dict:
    bench = bench or load_benchmark()
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in {BENCHMARK.name}")


def load_config(name: str, bench: dict | None = None) -> dict:
    return json.loads((ROOT / config_entry(name, bench)["file"]).read_text())


def load_traffic(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The configuration's plain reference, beside its file."""
    ref = config["reference"]
    return _load_module(BENCH_DIR / "configs" / f"{ref}.py",
                        f"chipbench_ref_{ref}")


def program_module(config: dict):
    """The program side of a configuration, by its published
    model_type."""
    prog = config["model_type"]
    return _load_module(BENCH_DIR / "programs" / f"{prog}.py",
                        f"chipbench_program_{prog}")


def metric_reader(name: str):
    return _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                        "chipbench_metric_" + name.replace(".", "_"))


def cell_metrics(cell_name: str, kind: str, bench: dict | None = None):
    """The `end_to_end` or `per_layer` entries this cell reports: those
    with no `workloads` key, and those that list the cell."""
    bench = bench or load_benchmark()
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]
