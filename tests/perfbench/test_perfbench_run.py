"""run.py end to end at the rehearsal size on the CPU: the last line has
the contract's keys and no CPU number under a device metric's name;
without a chip a real run ends with no result."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest

ROOT = manifest.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(*args, cwd=ROOT, extra_path=()):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    # one thread per device: the suite's other workers need the cores
    env["XLA_FLAGS"] = "--xla_cpu_multi_thread_eigen=false"
    env["BENCH_RUN"] = "ignored"
    env["PYTHONPATH"] = os.pathsep.join([str(p) for p in extra_path]
                                        + [env.get("PYTHONPATH", "")])
    return subprocess.run(
        [sys.executable, "-m", "perfbench.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _cells():
    return [w["name"] for w in manifest.load_manifest()["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_rehearsal_prints_the_contracts_line(cell):
    bench = manifest.load_manifest()
    proc = _run("--workload", cell, "--seed", str(2 ** 31 + 17),
                "--seconds", "2", "--trace", "0", "--rehearse")
    out = _last_line(proc)
    assert set(out) == RESULT_KEYS
    # each number `correct` compared beside its limit: the line's last key,
    # and the last lines of standard error
    assert list(out)[-1] == "compared" and out["compared"]
    said = proc.stderr.strip().splitlines()[-len(out["compared"]):]
    for line, (name, pair) in zip(said, out["compared"].items()):
        assert set(pair) == {"value", "limit"}
        assert pair["value"] <= pair["limit"]
        assert line == (f"compared {name} = {pair['value']!r} "
                        f"limit {pair['limit']!r}")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["device"]) == DEVICE_KEYS
    assert out["device"]["platform"] == "cpu"
    chips = manifest.find(bench["workloads"], cell, "workload")["chips"]
    assert out["device"]["count"] == chips
    want = {m["name"] for m in
            manifest.metrics_of_cell(bench, "end_to_end", cell)}
    # every metric of the cell, and none under its device name
    assert set(out["metrics"]) == {f"cpu_rehearsal.{n}" for n in want}
    for m in out["metrics"].values():
        # (a gap between tokens can read 0 here: the toy engine is faster
        # than the collector's poll, which the chip's never is)
        assert set(m) == {"value", "unit"} and m["value"] >= 0


@pytest.mark.parametrize("cell", [c for c in _cells() if "4chip" not in c][:2])
def test_traced_rehearsal_reports_layers_and_a_breakdown(cell):
    bench = manifest.load_manifest()
    out = _last_line(_run("--workload", cell, "--seed", "5", "--seconds", "2",
                          "--trace", "1", "--rehearse"))
    assert set(out) == RESULT_KEYS | {"breakdown"}
    assert set(out["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in out["breakdown"].values())
    assert any(name.startswith("pb.")
               for name, _ in out["breakdown"]["idle_gaps"])
    layer = {m["name"] for m in
             manifest.metrics_of_cell(bench, "per_layer", cell)}
    got = {n.removeprefix("cpu_rehearsal.") for n in out["metrics"]}
    # host-side layers are there; the CPU has no device lane, so every
    # reader of the device trace finds nothing and is left out
    assert got and got <= layer
    for m in bench["per_layer"]:
        if m["source"] == "device_trace":
            assert m["name"] not in got
    assert not set(out["metrics"]) & layer


def test_notes_are_written_beside_the_result(tmp_path):
    """--notes (builder): the run's checks, the histogram of token gaps and
    the device's memory statistics, one JSON line a run, appended."""
    cell = next(c for c in _cells() if "serve" in c)
    notes = tmp_path / "out" / "notes.jsonl"
    for seed in ("7", "8"):
        out = _last_line(_run("--workload", cell, "--seed", seed, "--seconds",
                              "2", "--trace", "0", "--rehearse",
                              "--notes", str(notes)))
    lines = [json.loads(x) for x in notes.read_text().splitlines()]
    assert [x["seed"] for x in lines] == [7, 8]
    last = lines[-1]
    assert last["workload"] == cell and last["result"] == out
    assert all(last["notes"]["checks"].values())
    gaps = last["notes"]["itl_histogram_10ms"]
    assert sum(gaps.values()) == last["notes"]["itl_gaps"] > 0
    assert last["notes"]["logit_atol"] > last["notes"]["decode_logit_diff"]
    assert "memory_stats" in last["notes"] and "setup_marks_s" in last["notes"]


def test_without_a_chip_there_is_no_result():
    proc = _run("--workload", _cells()[0], "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "CPU" in proc.stderr or "cpu" in proc.stderr


def test_an_unknown_workload_is_an_error():
    proc = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and "no-such-cell" in proc.stderr
