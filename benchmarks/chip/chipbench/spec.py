"""Where a cell's parts live, found by the names in ``BENCHMARK.json``.

* the cell: an entry of ``workloads``;
* its configuration: the ``file`` of the entry of ``configs`` it names;
* the configuration's join-key distribution: ``keys/<kind>.py`` beside
  this package, a function ``column(rng, n, *, domain, params, batch,
  relation)``;
* its traffic mix: ``traffic/<traffic>.json`` beside this package;
* each metric: ``metrics/<metric>.py`` beside this package, a reader with
  ``read(run) -> float | None``;
* each control of the job path (``run.py --control <name>`` on a job
  cell): ``controls/<name>.py`` beside this package, a factory
  ``make_program(cell, devices, settings, trace)`` of what runs in the
  batch entry's place.

Adding a cell, a configuration, a key distribution, a mix, a metric or a
control is adding files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    key_column: Callable  # the configuration's keys/<kind>.py ``column``
    end_to_end: list[dict]  # the end-to-end metrics this cell reports
    per_layer: list[dict]  # the per-layer metrics this cell reports


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(
    name: str, bench: dict | None = None, root: Path = ROOT, bench_dir: Path = BENCH_DIR
) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads(traffic_file(w["traffic"], bench_dir).read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [
        m for m in bench["per_layer"]
        if _reports(m, name) and m["moves"] in e2e_names
    ]
    key_column = load_key_column(config["keys"]["kind"], bench_dir)
    return Cell(name, int(w["chips"]), config, mix, key_column, e2e, layer)


def traffic_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


def key_file(kind: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "keys" / f"{kind}.py"


def control_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "controls" / f"{name}.py"


def _load(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load(metric_file(name, bench_dir), f"chipbench_metric_{name}").read


def load_key_column(kind: str, bench_dir: Path = BENCH_DIR):
    """The ``column`` function of ``keys/<kind>.py``."""
    return _load(key_file(kind, bench_dir), f"chipbench_keys_{kind}").column


def load_control(name: str, bench_dir: Path = BENCH_DIR):
    """The ``make_program`` factory of ``controls/<name>.py``."""
    return _load(control_file(name, bench_dir), f"chipbench_control_{name}").make_program
