"""BENCHMARK.json against its contract, and every file it names."""

import json
import re
from pathlib import Path

import pytest

from perfbench import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def _line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits(bench):
    assert set(bench) == KEYS
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= len(bench["command"]) <= 32
    for word in bench["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank")) and "embd" not in key


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = manifest.load_cell(bench, w["name"])     # both files resolve
        assert cell["traffic_file"]["kind"] in ("train", "serve")
        manifest.job(cell["traffic_file"]["kind"])
        manifest.family(cell["config_file"]["family"])


def _metric_checks(m, group):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES


def test_end_to_end_metrics(bench):
    e2e = bench["end_to_end"]
    assert 1 <= len(e2e) <= 16
    assert "setup_s" in [m["name"] for m in e2e]
    for m in e2e:
        _metric_checks(m, "end_to_end")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in bench["workloads"]:
        mine = [m["name"] for m in
                manifest.metrics_of_cell(bench, "end_to_end", w["name"])]
        assert "setup_s" in mine and len(mine) >= 2


def test_per_layer_metrics_move_a_metric_their_cells_report(bench):
    layer = bench["per_layer"]
    assert 1 <= len(layer) <= 128
    names = [m["name"] for m in bench["end_to_end"] + layer]
    assert len(set(names)) == len(names)
    cells = [w["name"] for w in bench["workloads"]]
    for m in layer:
        _metric_checks(m, "per_layer")
        assert _line(m["layer"])
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = [e["name"] for e in
                        manifest.metrics_of_cell(bench, "end_to_end", cell)]
            assert m["moves"] in reported, (m["name"], cell)
    for cell in cells:
        assert manifest.metrics_of_cell(bench, "per_layer", cell)


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_every_metric_has_its_file_and_reducer(bench, group):
    for m in bench[group]:
        spec = manifest.metric_spec(group, m["name"])
        for key in ("unit", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        if group == "per_layer":
            assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert callable(manifest.reducer(spec["reducer"]))


def test_files_under_paths_are_named_from_legal_characters(bench):
    for p in bench["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert PATH.match(str(f.relative_to(ROOT))), f


def test_check_budget_fits(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200
    assert total <= 43200
