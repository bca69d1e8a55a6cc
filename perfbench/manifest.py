"""BENCHMARK.json and the files it names.

A cell names a configuration and a traffic mix; each is a file found by
that name.  A metric names a file of its own under ``end_to_end/`` or
``layer_metrics/`` that says which reducer reads it and from what.
Nothing here, and nothing in run.py, names a cell, a configuration, a
traffic mix or a metric.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH_DIR = Path(__file__).resolve().parent
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r} "
                   f"(have {[e['name'] for e in entries]})")


def load_cell(manifest: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell with its configuration and traffic files read in."""
    cell = dict(find(manifest["workloads"], workload, "workload"))
    config_entry = find(manifest["configs"], cell["config"], "config")
    cell["config_file"] = json.loads((root / config_entry["file"]).read_text())
    cell["traffic_file"] = json.loads(
        (root / "perfbench" / "traffic" / f"{cell['traffic']}.json")
        .read_text())
    return cell


def metric_spec(group: str, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The metric's own file: unit, source, reducer, and its parameters."""
    return json.loads(
        (bench_dir / METRIC_DIRS[group] / f"{name}.json").read_text())


def metrics_of_cell(manifest: dict, group: str, workload: str) -> list:
    """The metrics of that group which this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def reducer(name: str):
    return importlib.import_module(f"perfbench.reducers.{name}").reduce


def job(kind: str):
    return importlib.import_module(f"perfbench.jobs.{kind}")


def family(name: str):
    return importlib.import_module(f"perfbench.families.{name}")
