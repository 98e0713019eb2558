"""Find a cell and everything it needs by name, from ``BENCHMARK.json``
and the files it names. Nothing here knows a configuration, mix or
metric by name."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict                 # the configuration file's contents
    traffic_name: str
    traffic: Dict                # the mix's data file
    end_to_end: List[Dict]       # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]
    limits: Dict                 # bench/limits/<cell>.json


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(workload: str) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``."""
    with open(ROOT / "BENCHMARK.json") as f:
        return build(json.load(f), workload)


def build(bench: Dict, workload: str) -> Cell:
    """The cell ``workload`` from entries laid out as ``BENCHMARK.json``
    lays them out, with the files they name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    from harness import traffic
    with open(BENCH / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"],
        traffic=traffic.load(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        limits=limits)


def driver(cell: Cell):
    """The module that builds and drives this configuration's model:
    ``bench/drivers/<driver>.py``."""
    name = cell.config["driver"]
    return load_module(BENCH / "drivers" / f"{name}.py", f"driver_{name}")


def reference(cell: Cell):
    """The configuration's plain reference: ``bench/reference/<name>.py``
    (imports nothing of the program)."""
    name = cell.config["reference"]
    return load_module(BENCH / "reference" / f"{name}.py", f"reference_{name}")


def metric_reader(name: str):
    """``bench/metrics/<metric>.py``: its ``read(run)`` returns the value,
    or None when the run holds nothing to read."""
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "metric_" + name.replace(".", "_"))


def peaks(device_kind: str) -> Dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
