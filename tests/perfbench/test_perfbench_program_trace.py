"""The program's own spans in a capture (perfbench/program_trace.py) and the
reducers that read them: on made-up planes, on cases whose answers are
known, and on a small trace recorded on the v5e
(fixtures/serve_program_trace_sample.json: 0.6 s of the serving cell,
PR 25), where the answers are found again by brute force on a
1-microsecond grid.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import manifest, program_trace

ROOT = manifest.ROOT
FIXTURE = Path(__file__).with_name("fixtures") / \
    "serve_program_trace_sample.json"
NEW_METRICS = {
    "engine.decode_dispatch_ms", "engine.decode_pull_ms",
    "engine.decode_host_ms", "engine.prefill_scatter_ms",
    "device.decode_idle_in_dispatch_ms", "device.decode_idle_in_pull_ms",
    "scheduler.queue_wait_mean_ms"}


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = \
            name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_planes_become_spans_by_thread_and_operations_cut_to_the_window():
    """The layout a v5e trace has (my chip run, PR 25): the engine's loop
    and a submitter are two lines of /host:CPU, both called "python"."""
    planes = [
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Ev("jit__unknown(1)", 0, 9_000)]),
            _Line("XLA Ops", [
                _Ev("%fusion.7 = f32[8]{0} fusion()", 500, 1_000),
                _Ev("%copy.1 = f32[8]{0} copy()", 3_000, 2_000),
                _Ev("%zero = f32[] constant(0)", 6_000, 0)])]),
        _Plane("/host:CPU", [
            _Line("python", [_Ev("pb.window", 1_000, 8_000),
                             _Ev("pb.step", 1_500, 6_000),
                             _Ev("llm.step", 1_600, 5_000),
                             _Ev("llm.decode", 2_000, 4_000),
                             _Ev("llm.decode.pull", 2_500, 3_000),
                             _Ev("$engine.py:1 step", 1_700, 100)]),
            _Line("python", [_Ev("llm.submit", 4_000, 200)])]),
        _Plane("/host:metadata", []),
    ]
    got = program_trace.from_planes(planes)
    assert got["window"] == [1e-6, 9e-6]
    assert sorted(got["spans"]) == ["python#0", "python#1"]
    loop, other = got["spans"]["python#0"], got["spans"]["python#1"]
    assert [e[0] for e in loop] == ["llm.step", "llm.decode",
                                    "llm.decode.pull"]
    assert [e[1:] for e in loop] == [pytest.approx(x) for x in (
        [1.6e-6, 5e-6], [2e-6, 4e-6], [2.5e-6, 3e-6])]
    assert other == [["llm.submit", pytest.approx(4e-6), pytest.approx(2e-7)]]
    ops = got["ops"]["/device:TPU:0"]
    assert [op[0] for op in ops] == ["fusion.7 f32[8]", "copy.1 f32[8]"]
    assert ops[0][1:] == pytest.approx([1e-6, 5e-7])        # cut at the edge
    assert program_trace.loop_spans(got) == got["spans"]["python#0"]
    idle = program_trace.idle_seconds_by_span(got)
    # busy 1.0-1.5 and 3-5 us of the window 1-9 us
    assert idle["llm.decode.pull"] == pytest.approx(1e-6)      # 2.5-3, 5-5.5
    assert idle["llm.decode"] == pytest.approx(1e-6)        # 2-2.5, 5.5-6
    assert idle["llm.step"] == pytest.approx(1e-6)          # 1.6-2, 6-6.6
    assert idle["_no_span_"] == pytest.approx(2.5e-6)


def test_a_capture_without_a_window_span_is_an_error():
    with pytest.raises(RuntimeError, match="pb.window"):
        program_trace.from_planes([_Plane("/host:CPU", [
            _Line("python", [_Ev("llm.step", 0, 10)])])])


SPANS = [["a", 0.0, 10.0],
         ["a.x", 1.0, 2.0], ["a.y", 3.0, 1.0],          # adjacent children
         ["a.z", 5.0, 4.0], ["a.z.deep", 6.0, 2.0],     # a nested one
         ["b", 10.0, 5.0], ["b.x", 11.0, 1.0],
         ["a", 20.0, 4.0], ["a.x", 21.0, 1.0]]


def test_self_time_with_adjacent_and_with_nested_children():
    a, z = SPANS[0], SPANS[3]
    assert program_trace.self_seconds(SPANS, a) == pytest.approx(3.0)
    assert program_trace.self_seconds(SPANS, z) == pytest.approx(2.0)
    assert program_trace.self_seconds(SPANS, SPANS[4]) == pytest.approx(2.0)
    assert [k[0] for k in program_trace.inside(SPANS, a)] == \
        ["a.x", "a.y", "a.z", "a.z.deep"]


def test_per_parent_sums_named_spans_at_any_depth_and_skips_cut_parents():
    window = (-1.0, 23.5)           # the last "a" is cut by the window
    assert program_trace.per_parent(SPANS, window, "a", ["a.x"]) == \
        pytest.approx([2.0])
    assert program_trace.per_parent(
        SPANS, window, "a", ["a.x", "a.z.deep"]) == pytest.approx([4.0])
    assert program_trace.per_parent(
        SPANS, window, "a", ["a.x", "a.z.deep"], rest=True) == \
        pytest.approx([6.0])
    # a span inside another of the names is covered once, not twice
    assert program_trace.per_parent(
        SPANS, window, "a", ["a.z", "a.z.deep"]) == pytest.approx([4.0])
    assert program_trace.per_parent(
        SPANS, (-1.0, 30.0), "a", ["a.x"]) == pytest.approx([2.0, 1.0])
    assert program_trace.per_parent(
        SPANS, window, "b", ["b.x"], having="b.y") == []
    assert program_trace.stat([3.0, 1.0, 8.0], "median") == 3.0
    assert program_trace.stat([3.0, 1.0, 8.0], "mean") == 4.0
    assert program_trace.stat([], "median") is None


# -------------------------------------------------- the recorded chip trace
@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def _raster(intervals, start, end, step=1e-6):
    grid = np.zeros(int(round((end - start) / step)), bool)
    for s, e in intervals:
        grid[int(round((s - start) / step)):int(round((e - start) / step))] = 1
    return grid


def test_recorded_trace_holds_the_loop_and_a_submitter(recorded):
    assert len(recorded["spans"]) == 2
    loop = program_trace.loop_spans(recorded)
    names = {name for name, _, _ in loop}
    assert {"llm.step", "llm.step.admit", "llm.step.plan",
            "llm.step.publish", "llm.prefill", "llm.prefill.dispatch",
            "llm.prefill.pull", "llm.prefill.scatter", "llm.decode",
            "llm.decode.slots", "llm.decode.tables", "llm.decode.dispatch",
            "llm.decode.pull", "llm.decode.commit"} <= names
    other = next(v for v in recorded["spans"].values() if v is not loop)
    assert {name for name, _, _ in other} == {"llm.submit"}


def test_recorded_trace_spans_per_parent_by_brute_force(recorded):
    loop, window = program_trace.loop_spans(recorded), recorded["window"]
    start, end = window
    for parent, names in (("llm.decode", ["llm.decode.pull"]),
                          ("llm.decode", ["llm.decode.dispatch"]),
                          ("llm.prefill", ["llm.prefill.scatter"]),
                          ("llm.step", ["llm.decode.dispatch",
                                        "llm.decode.pull"])):
        want = []
        for name, s, d in loop:
            if name != parent or s <= start or s + d >= end:
                continue
            grid = _raster([(ks, ks + kd) for kn, ks, kd in loop
                            if kn in names and s <= ks and ks + kd <= s + d],
                           start, end)
            want.append(grid.sum() * 1e-6)
        got = program_trace.per_parent(loop, window, parent, names)
        assert len(got) == len(want) >= 1
        assert got == pytest.approx(want, abs=3e-6)
    # the one whole decode step of the cut: its time is the pull
    pull = program_trace.per_parent(loop, window, "llm.decode",
                                    ["llm.decode.pull"])
    dispatch = program_trace.per_parent(loop, window, "llm.decode",
                                        ["llm.decode.dispatch"])
    assert 0.2 < pull[0] < 0.3 and dispatch[0] < 0.01


def test_recorded_trace_idle_under_spans_by_brute_force(recorded):
    start, end = recorded["window"]
    ops = next(iter(recorded["ops"].values()))
    idle = ~_raster([(s, s + d) for _, s, d in ops], start, end)
    loop = program_trace.loop_spans(recorded)
    owner = np.full(idle.size, -1)
    for i, (_, s, d) in enumerate(loop):    # sorted by start: the innermost
        a = max(0, int(round((s - start) / 1e-6)))          # span is
        owner[a:max(0, int(round((s + d - start) / 1e-6)))] = i  # written last
    got = program_trace.idle_seconds_by_span(recorded)
    for name in ("llm.decode.pull", "llm.prefill.scatter",
                 "llm.prefill.pull", "llm.decode.dispatch"):
        mine = np.isin(owner, [i for i, e in enumerate(loop) if e[0] == name])
        assert got[name] == pytest.approx((idle & mine).sum() * 1e-6,
                                          abs=2e-4), name
    assert sum(got.values()) == pytest.approx(idle.sum() * 1e-6, abs=2e-4)
    # the device waits while the host waits: most idle time is under the pull
    assert max(got, key=got.get) == "llm.decode.pull"


# ------------------------------------------------------------- the reducers
@pytest.fixture
def recorded_run(recorded, monkeypatch):
    """A traced run whose capture is the recorded trace."""
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: recorded if facts.get("trace") else None)
    return {"trace": {"window": recorded["window"]}}


def _reduce(name, facts):
    spec = manifest.metric_spec("per_layer", name)
    return manifest.reducer(spec["reducer"])(facts, spec["params"])


def test_new_metrics_are_in_the_manifest_for_the_serving_cell_only():
    bench = manifest.load_manifest()
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    # by name, wherever later PRs' entries put them: each once, reported by
    # the cell they were added for and by serving cells alone
    assert sorted(m["name"] for m in mine) == sorted(NEW_METRICS)
    for m in mine:
        assert "gpt2-xl-1558m.serve-chat-steady" in m["workloads"]
        for name in m["workloads"]:
            cell = manifest.load_cell(bench, name)
            assert cell["traffic_file"]["kind"] == "serve", (m["name"], name)
        assert m["better"] == "lower" and m["unit"] == "ms"


def test_span_metrics_of_the_recorded_trace_add_up(recorded, recorded_run):
    dispatch = _reduce("engine.decode_dispatch_ms", recorded_run)
    pull = _reduce("engine.decode_pull_ms", recorded_run)
    host = _reduce("engine.decode_host_ms", recorded_run)
    scatter = _reduce("engine.prefill_scatter_ms", recorded_run)
    loop = program_trace.loop_spans(recorded)
    start, end = recorded["window"]
    step = next(d for name, s, d in loop if name == "llm.step"
                and s > start and s + d < end
                and any(k == "llm.decode" and s <= ks < s + d
                        for k, ks, _ in loop))
    assert dispatch + pull + host == pytest.approx(1e3 * step, abs=1e-6)
    assert host < 3.0 and 100.0 < scatter < 200.0
    in_pull = _reduce("device.decode_idle_in_pull_ms", recorded_run)
    in_dispatch = _reduce("device.decode_idle_in_dispatch_ms", recorded_run)
    assert in_pull > 50.0 and in_dispatch < 5.0


@pytest.mark.parametrize("name", sorted(NEW_METRICS - {
    "scheduler.queue_wait_mean_ms"}))
def test_trace_metrics_are_none_without_the_programs_spans(name, monkeypatch):
    """The parent of the PR that added the spans, an untraced run, and a
    capture that is gone: nothing, and no error."""
    no_spans = {"window": [0.0, 1.0], "spans": {},
                "ops": {"/device:TPU:0": [["fusion.1 f32[8]", 0.1, 0.2]]}}
    monkeypatch.setattr(program_trace, "of_run", lambda facts: no_spans)
    assert _reduce(name, {"trace": {}}) is None
    monkeypatch.undo()
    assert _reduce(name, {"trace": None}) is None
    monkeypatch.setattr(program_trace, "SCRATCH", ROOT / "no-such-dir")
    assert _reduce(name, {"trace": {"window": [0, 1]}}) is None


def test_idle_metrics_are_none_where_no_device_was_traced(recorded,
                                                          monkeypatch):
    """A rehearsal on the CPU has spans and no device plane: its idle time
    is not a number of the device."""
    cpu = {**recorded, "ops": {}}
    monkeypatch.setattr(program_trace, "of_run", lambda facts: cpu)
    assert _reduce("device.decode_idle_in_pull_ms", {"trace": {}}) is None
    assert _reduce("engine.decode_pull_ms", {"trace": {}}) > 0


def test_histogram_mean_reads_the_programs_catalog_in_this_process():
    from ray_tpu.util import metrics

    params = {"histogram": "rtpu_test_pb_queue_seconds", "scale": 1000.0}
    reduce = manifest.reducer("catalog_histogram_mean")
    assert reduce({}, params) is None                   # no such histogram
    hist = metrics.Histogram(params["histogram"], "test", tag_keys=("model",))
    assert reduce({}, params) is None                   # never observed
    hist.observe(0.1, tags={"model": "a"})
    hist.observe(0.3, tags={"model": "b"})
    assert reduce({}, params) == pytest.approx(200.0)


def test_rehearsal_prints_the_host_span_metrics_and_no_device_number():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "gpt2-xl-1558m.serve-chat-steady", "--seed", "2147483999",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    got = out["metrics"]
    for name in ("engine.decode_dispatch_ms", "engine.decode_pull_ms",
                 "engine.decode_host_ms", "engine.prefill_scatter_ms",
                 "scheduler.queue_wait_mean_ms"):
        assert got[f"cpu_rehearsal.{name}"]["value"] > 0, name
    assert not [k for k in got if "device." in k]
    step = got["cpu_rehearsal.engine.decode_step_ms"]["value"]
    parts = sum(got[f"cpu_rehearsal.engine.decode_{p}_ms"]["value"]
                for p in ("dispatch", "pull", "host"))
    assert parts < 2 * step + 5.0       # medians and a mean of one loop
