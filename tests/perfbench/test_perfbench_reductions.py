"""The reductions from samples and traces to numbers, on cases whose
answers are known, and on a small trace recorded on the v5e
(fixtures/serve_trace_sample.json: 0.6 s of the serving cell, PR 24),
where the answers are found again by brute force on a 1-microsecond grid.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import stats, trace

FIXTURE = Path(__file__).with_name("fixtures") / "serve_trace_sample.json"


def test_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 90, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([4.0], 95) == 4.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_percentile_interpolates_between_ranks_and_mean_is_plain():
    assert stats.percentile([10.0, 20.0], 95) == pytest.approx(19.5)
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert stats.mean([1.0, 2.0, 6.0]) == pytest.approx(3.0)
    with pytest.raises(ValueError):
        stats.mean([])


def _trace(device, host=(), window=(0.0, 10.0), later=None):
    return {"device": {"/device:TPU:0": [list(e) for e in device]},
            "device_async": {"/device:TPU:0": [list(e) for e in later or ()]},
            "host": [list(h) for h in host], "window": list(window)}


def test_busy_is_a_union_not_a_sum():
    t = _trace([("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0)])
    assert trace.busy_seconds(t) == pytest.approx(4.0)
    assert trace.idle_share(t) == pytest.approx(0.6)


def test_longest_gaps_say_where_in_the_window_the_device_stood_still():
    t = _trace([("a", 1.0, 2.0), ("b", 2.5, 1.5), ("c", 9.0, 0.5)])
    assert trace.longest_gaps(t, 2) == [[4.0, 5.0], [0.0, 1.0]]


def test_busy_is_averaged_over_devices():
    t = _trace([("a", 0.0, 2.0)])
    t["device"]["/device:TPU:1"] = [["a", 0.0, 4.0]]
    assert trace.busy_seconds(t) == pytest.approx(3.0)


def test_wrappers_are_busy_time_but_no_operation_of_their_own():
    t = _trace([("while.2 (s32[]", 0.0, 4.0), ("fusion.1 f32[8]", 0.5, 1.0),
                ("tpu_custom_call.3 bf16[8]", 2.0, 1.0)])
    assert trace.busy_seconds(t) == pytest.approx(4.0)
    assert [n for n, _ in trace.top_ops(t)] == ["fusion.1 f32[8]",
                                                "tpu_custom_call.3 bf16[8]"]
    assert trace.op_seconds(t, "tpu_custom_call") == pytest.approx(1.0)
    assert trace.op_seconds(t, "while") == 0.0


def test_kernel_time_in_a_step_is_its_share_of_the_trace_times_the_step():
    from perfbench.reducers import op_ms_per_step
    t = _trace([("fusion.1 f32[8]", 0.0, 3.0),
                ("tpu_custom_call.3 bf16[8]", 3.0, 1.0),
                ("tpu_custom_call.4 bf16[8]", 6.0, 1.5)])
    facts = {"trace": t, "window_s": 40.0, "steps": 100}    # 400 ms a step
    got = op_ms_per_step.reduce(facts, {"substring": "tpu_custom_call"})
    assert got == pytest.approx(0.25 * 400.0)
    assert op_ms_per_step.reduce({"trace": None}, {"substring": "x"}) is None


def test_short_name_keeps_the_operation_and_its_first_shape():
    raw = ("%fusion.69 = (f32[1024,12]{0,1:T(8,128)}, f32[4]) "
           "fusion(f32[1024,12] %custom-call.1), kind=kLoop")
    assert trace.short_name(raw) == "fusion.69 f32[1024,12]"
    assert "custom-call" not in trace.short_name(raw)
    assert trace.short_name("dot_general.1") == "dot_general.1"


def test_exposed_collective_time_is_what_compute_does_not_hide():
    # compute 0-4 and 6-8; an all-gather 3-5 (1 s hidden, 1 s exposed);
    # an asynchronous collective-permute 5-7 (1 s exposed, 1 s hidden)
    t = _trace([("fusion.1", 0.0, 4.0), ("all-gather.2", 3.0, 2.0),
                ("fusion.3", 6.0, 2.0)],
               later=[("collective-permute.7", 5.0, 2.0)])
    got = trace.exposed_collectives(t)
    assert got["collective_s"] == pytest.approx(4.0)
    assert got["compute_s"] == pytest.approx(6.0)
    assert got["exposed_s"] == pytest.approx(2.0)
    assert got["exposed_s_by_kind"] == pytest.approx(
        {"all_gather": 1.0, "ppermute": 1.0})


def test_a_loop_around_the_step_hides_no_collective():
    t = _trace([("while.1 (s32[]", 0.0, 10.0), ("all-reduce.2", 2.0, 1.0)])
    assert trace.exposed_collectives(t)["exposed_s"] == pytest.approx(1.0)


def test_idle_gaps_go_to_the_innermost_span():
    # device busy 1-2 and 6-7 in a window 0-10: idle 8 s.  The host is in
    # pb.step 0-5 with pb.decode.run 2-4 inside, and in no span after 5.
    t = _trace([("a", 1.0, 1.0), ("b", 6.0, 1.0)],
               host=[("pb.step", 0.0, 5.0), ("pb.decode.run", 2.0, 2.0)])
    gaps = dict(trace.idle_gaps(t))
    assert gaps == pytest.approx(
        {"pb.step": 2.0, "pb.decode.run": 2.0, "_no_span_": 4.0})
    assert sum(gaps.values()) == pytest.approx(
        10.0 * trace.idle_share(t))


def test_steps_are_named_after_the_call_they_hold():
    t = _trace([], host=[("pb.step", 0.0, 1.0), ("pb.decode.run", 0.2, 0.5),
                         ("pb.step", 1.0, 1.0), ("pb.prefill.run", 1.1, 0.2),
                         ("pb.step", 2.0, 0.1)])
    named = trace.rename_by_child(
        t, "pb.step", {"pb.decode.run": "pb.decode",
                       "pb.prefill.run": "pb.prefill"}, "pb.step_idle")
    assert [h[0] for h in named["host"] if h[2] in (1.0, 0.1)] == \
        ["pb.decode", "pb.prefill", "pb.step_idle"]


def test_device_time_inside_spans():
    t = _trace([("a", 0.5, 1.0), ("b", 2.0, 2.0)],
               host=[("pb.decode", 0.0, 1.0), ("pb.decode", 3.0, 2.0),
                     ("pb.prefill", 1.0, 2.0)])
    seconds, count = trace.device_seconds_in_spans(t, "pb.decode")
    assert (seconds, count) == (pytest.approx(1.5), 2)
    assert trace.device_seconds_in_spans(t, "pb.none") == (0.0, 0)


def test_clip_cuts_events_at_the_edges():
    t = _trace([("a", 0.0, 2.0), ("b", 3.0, 4.0)], host=[("pb.x", 1.0, 9.0)])
    cut = trace.clip_to_window(t, 1.0, 5.0)
    assert cut["device"]["/device:TPU:0"] == [["a", 1.0, 1.0], ["b", 3.0, 2.0]]
    assert cut["host"] == [["pb.x", 1.0, 4.0]] and cut["window"] == [1.0, 5.0]


# ------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def _raster(intervals, start, end, step=1e-6):
    n = int(round((end - start) / step))
    grid = np.zeros(n, bool)
    for s, e in intervals:
        grid[int(round((s - start) / step)):int(round((e - start) / step))] = 1
    return grid


def test_recorded_trace_busy_and_idle_by_brute_force(recorded):
    start, end = recorded["window"]
    events = next(iter(recorded["device"].values()))
    assert len(events) > 100 and recorded["host"]
    grid = _raster([(s, s + d) for _, s, d in events], start, end)
    assert trace.busy_seconds(recorded) == pytest.approx(
        grid.sum() * 1e-6, rel=2e-3)
    assert trace.idle_share(recorded) == pytest.approx(
        1 - grid.mean(), abs=2e-3)
    # the serving engine leaves the device idle most of the time (S3)
    assert 0.5 < trace.idle_share(recorded) < 0.95


def test_recorded_trace_idle_gaps_by_brute_force(recorded):
    start, end = recorded["window"]
    events = next(iter(recorded["device"].values()))
    idle = ~_raster([(s, s + d) for _, s, d in events], start, end)
    gaps = dict(trace.idle_gaps(recorded, n=100))
    assert sum(gaps.values()) == pytest.approx(idle.sum() * 1e-6, rel=2e-3)
    run = _raster([(s, s + d) for n, s, d in recorded["host"]
                   if n == "pb.decode.run"], start, end)
    assert gaps["pb.decode.run"] == pytest.approx(
        (idle & run).sum() * 1e-6, rel=5e-3)
    # inside the decode call the host is moving the pool: most of the idleness
    assert gaps["pb.decode.run"] > 0.5 * sum(gaps.values())


def test_recorded_trace_device_time_in_decode_steps(recorded):
    start, end = recorded["window"]
    events = next(iter(recorded["device"].values()))
    busy = _raster([(s, s + d) for _, s, d in events], start, end)
    spans = _raster([(s, s + d) for n, s, d in recorded["host"]
                     if n == "pb.decode"], start, end)
    seconds, count = trace.device_seconds_in_spans(recorded, "pb.decode")
    assert count >= 2
    assert seconds == pytest.approx((busy & spans).sum() * 1e-6, rel=5e-3)


# --------------------------------------- reading the profiler's planes
class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_planes_of_a_tpu_trace_become_operations_and_spans():
    """The layout a v5e trace has (my chip run, PR 24): per device the
    lines Steps, XLA Modules, XLA Ops, Async XLA Ops, TC Overlay."""
    hlo = "%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] %custom-call.2)"
    planes = [
        _Plane("/device:TPU:0", [
            _Line("Steps", [_Ev("1", 0, 10_000)]),
            _Line("XLA Modules", [_Ev("jit_step(1)", 0, 10_000)]),
            _Line("XLA Ops", [_Ev(hlo, 1_000, 2_000),
                              _Ev("%tpu_custom_call.3 = bf16[4]{0} "
                                  "custom-call()", 4_000, 1_000),
                              _Ev("%zero = f32[] constant(0)", 6_000, 0)]),
            _Line("Async XLA Ops", [_Ev("%collective-permute.5 = f32[4]{0} "
                                        "collective-permute()", 2_000, 3_000)]),
        ]),
        _Plane("/host:CPU", [
            _Line("python3", [_Ev("pb.step", 500, 6_000),
                              _Ev("$engine.py:1 step", 600, 100)])]),
        _Plane("/host:metadata", []),
    ]
    got = trace.from_planes(planes)
    assert got["device"] == {"/device:TPU:0": [
        ["fusion.7 f32[8,128]", 1e-6, 2e-6],
        ["tpu_custom_call.3 bf16[4]", 4e-6, 1e-6]]}
    assert got["device_async"] == {"/device:TPU:0": [
        ["collective-permute.5 f32[4]", 2e-6, 3e-6]]}
    assert got["host"] == [["pb.step", 5e-7, 6e-6]]
    assert got["layout"]["/device:TPU:0"][2] == "XLA Ops"
    cut = trace.clip_to_window(got, 0.0, 1e-5)
    assert trace.busy_seconds(cut) == pytest.approx(3e-6)
    assert trace.exposed_collectives(cut)["exposed_s"] == pytest.approx(1e-6)
