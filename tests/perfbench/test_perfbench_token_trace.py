"""The program's token stamps and its loop's waits in a capture
(perfbench/token_trace.py) and the reducers of the eight metrics that read
them (PR 54): on a made-up trace whose answers are written beside it or
found again by brute force on a 1-microsecond grid, on captures that lack
the spans or the device, and in one rehearsal of a serving cell.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import manifest, program_trace, token_trace, trace

ROOT = manifest.ROOT
# The three serving cells whose tests do not hold their list of metrics to
# a fixed set.  tests/perfbench/test_perfbench_minicpm_sala.py and
# test_perfbench_afmoe.py do, and no PR but a ``benchmark`` PR edits them:
# until one does, the MiniCPM-SALA and Trinity cells list none of these, and
# ``device.idle_per_chunk_ms`` (theirs alone) has its file and no entry.
SERVING = ["gpt2-xl-1558m.serve-chat-steady", "falcon-h1-34b.serve-chat-busy",
           "lfm2-24b-a2b.serve-chat-busy-routed"]
CELLS = {name: SERVING for name in (
    "engine.token_gap_p50_ms", "engine.token_gap_p95_ms",
    "engine.first_token_p50_ms", "engine.compiles_in_window",
    "device.idle_unoffered_share", "device.idle_with_work_share",
    "device.idle_per_prefill_ms")}
UNLISTED = "device.idle_per_chunk_ms"


class _Ev:
    def __init__(self, name, start_us, dur_us, **stats):
        self.name, self.stats = name, list(stats.items())
        self.start_ns, self.duration_ns = start_us * 1000, dur_us * 1000


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _decode(start, end, step, seqs, *inside):
    return [_Ev("llm.decode", start, end - start, step=step, seqs=seqs),
            _Ev("llm.decode.dispatch", start + 50, 100), *inside]


def _read(start, step, tokens, seqs=None):
    """A pull and the commit behind it, 100 us each from ``start``."""
    named = {} if seqs is None else {"seqs": seqs}
    return [_Ev("llm.decode.pull", start, 100, step=step),
            _Ev("llm.decode.commit", start + 100, 100, step=step,
                tokens=tokens, **named)]


def _prefill(start, end, seq):
    return [_Ev("llm.prefill", start, end - start, seq=seq),
            _Ev("llm.prefill.dispatch", start + 50, 150),
            _Ev("llm.prefill.pull", start + 200, end - start - 300),
            _Ev("llm.prefill.scatter", end - 100, 100),
            _Ev("llm.prefill.commit", end, 100, seq=seq)]


def _step(start, end, *inside):
    return [_Ev("llm.step", start, end - start),
            _Ev("llm.step.admit", start, 100), *inside]


# The window is 1,000-11,000 us.  Sequence ids as a capture hands them
# back: "aa" a string, 77 a number (an id of digits alone), "cc", "dd".
LOOP = [
    _Ev("llm.idle", 900, 600, cause="empty"),
    *_step(1500, 2000, *_prefill(1550, 1850, 77)),
    *_step(2000, 3000, *_prefill(2100, 2800, "aa")),
    *_step(3000, 3600, *_decode(3050, 3550, 1, "aa|77")),
    *_step(3600, 4400, *_decode(3650, 4350, 2, "aa|77",
                                *_read(4100, 1, 2, "aa|77"))),
    # a prompt arrives: the step in flight is drained inside the admission
    _Ev("llm.step", 4400, 1000), _Ev("llm.step.admit", 4400, 400),
    _Ev("llm.decode.drain", 4450, 300, cause="admit"),
    *_read(4500, 2, 2, "aa|77"), *_prefill(4800, 5200, "cc"),
    # the head cannot fit: a step that runs nothing, then the wait
    *_step(5400, 5500), _Ev("llm.idle", 5500, 200, cause="blocked"),
    *_step(5700, 6300, *_decode(5750, 6250, 3, "aa|77|cc")),
    # cache pressure: the drain inside the slots, then "cc" is evicted
    *_step(6300, 7300, *_decode(
        6350, 7250, 4, "aa|77", _Ev("llm.decode.slots", 6600, 500),
        _Ev("llm.decode.drain", 6650, 300, cause="pressure"),
        *_read(6700, 3, 3, "aa|77|cc"),
        _Ev("llm.preempt", 7000, 50, seq="cc"))),
    _Ev("llm.step", 7300, 1000), _Ev("llm.step.admit", 7300, 100),
    _Ev("llm.decode.drain", 7400, 250, cause="admit"),
    *_read(7420, 4, 2, "aa|77"), *_prefill(7650, 8100, "cc"),
    *_step(8300, 8800, *_decode(8350, 8750, 5, "aa|77|cc")),
    *_step(8800, 9500, *_decode(8850, 9450, 6, "aa|77|cc",
                                *_read(9200, 5, 3, "aa|77|cc"))),
    # the tail: "cc" ended at the commit before, its row is discarded;
    # and a step whose every row was, which names nobody
    _Ev("llm.step", 9500, 300), _Ev("llm.step.admit", 9500, 50),
    _Ev("llm.decode.drain", 9560, 220, cause="tail"),
    *_read(9570, 6, 2, "aa|77"),
    _Ev("llm.decode.commit", 9785, 10, step=7, tokens=0),
    _Ev("llm.idle", 9800, 700, cause="empty"),
    *_step(10500, 11500, *_prefill(10600, 11200, "dd")),
    _Ev("llm.idle", 11500, 300, cause="error"),
]
SUBMITS = [_Ev("llm.submit", 990, 30, seq=77),        # before the window
           _Ev("llm.submit", 1950, 20, seq="aa"),
           _Ev("llm.submit", 10400, 20, seq="dd")]
BUSY = [(1600, 1900), (2200, 2750), (3150, 3900), (3900, 4450),
        (4900, 5150), (5900, 6500), (6800, 7450), (7750, 8050),
        (8400, 8900), (8900, 9600), (10700, 11100)]


def _planes(loop=LOOP, busy=BUSY, submits=SUBMITS):
    device = [_Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev(f"%fusion.{i} = f32[8]{{0}} fusion()", s, e - s)
        for i, (s, e) in enumerate(busy)])])] if busy else []
    return device + [_Plane("/host:CPU", [
        _Line("python", [_Ev("pb.window", 1000, 10000), *loop]),
        _Line("python", submits)])]


@pytest.fixture(scope="module")
def made_up():
    return token_trace.from_planes(_planes())


def _run_of(ttrace, monkeypatch):
    monkeypatch.setattr(token_trace, "of_run",
                        lambda facts: ttrace if facts.get("trace") else None)
    return {"trace": {"window": ttrace["window"]}}


def _reduce(name, facts):
    spec = manifest.metric_spec("per_layer", name)
    return manifest.reducer(spec["reducer"])(facts, spec["params"])


# ------------------------------------------------------------ the reader
def test_spans_keep_their_attributes_and_the_loops_line_is_found(made_up):
    assert made_up["window"] == pytest.approx([1e-3, 11e-3])
    loop = token_trace.loop_spans(made_up)
    # what starts after the window is not kept: the last wait, and the
    # scatter and commit of the prompt whose prefill straddles the edge
    assert len(loop) == sum(e.start_ns < 11_000_000 for e in LOOP) \
        == len(LOOP) - 3
    assert loop[0][0] == "llm.idle" and loop[0][3] == {"cause": "empty"}
    assert loop[0][1] == pytest.approx(0.9e-3)      # kept whole, not cut
    other = [v for v in made_up["spans"].values() if v is not loop]
    assert [[e[0] for e in v] for v in other] == [["llm.submit"] * 3]
    assert token_trace.covered(made_up)
    # the same window and operations as the reader of names and times
    plain = program_trace.from_planes(_planes())
    assert plain["ops"] == made_up["ops"]
    assert [e[:3] for e in loop] == program_trace.loop_spans(plain)


def test_a_commit_names_whom_it_served_whatever_the_capture_made_of_an_id():
    commit = ["llm.decode.commit", 0.0, 1.0, {"seqs": "aa|77|cc"}]
    assert token_trace.members(commit) == ["aa", "77", "cc"]
    alone = ["llm.decode.commit", 0.0, 1.0, {"seqs": 77, "tokens": 1}]
    assert token_trace.members(alone) == ["77"]
    assert token_trace.members(["llm.decode.commit", 0, 1, {"tokens": 0}]) \
        == []
    assert token_trace.members(["llm.prefill.commit", 0, 1, {"seq": 77}]) \
        == ["77"]


# -------------------------------------------------------------- the tokens
def test_gaps_run_from_commit_to_commit_of_one_sequence(made_up):
    """Ends of the commits that name each sequence, in microseconds:
    aa 2900 | 4300 4700 6900 7620 9400 9770; 77 1950 | the same six;
    cc 5300 | 6900, preempted, 8200 | 9400; dd's lies after the window."""
    both = [4300, 4700, 6900, 7620, 9400, 9770]
    want = [b - a for a, b in zip([2900] + both, both)] \
        + [b - a for a, b in zip([1950] + both, both)] \
        + [6900 - 5300, 9400 - 8200]
    got = token_trace.token_gaps(made_up)
    assert sorted(1e6 * g for g in got) == pytest.approx(sorted(want))
    # across the drained step a gap is short (the drain's own pull), across
    # the preemption there is none: 8200 - 6900 is in no list
    assert 1300 not in [round(1e6 * g) for g in got]
    assert min(got) == pytest.approx(370e-6)


def test_a_gap_counts_where_it_ends(made_up):
    early = {**made_up, "window": [1e-3, 4.5e-3]}
    assert sorted(1e6 * g for g in token_trace.token_gaps(early)) == \
        pytest.approx([1400, 2350])
    late = {**made_up, "window": [9.5e-3, 11e-3]}
    assert [1e6 * g for g in token_trace.token_gaps(late)] == \
        pytest.approx([370, 370])


def test_first_tokens_of_the_requests_submitted_in_the_window(made_up):
    """aa: 1950 -> 2900.  dd's first token comes after the window, 77 was
    submitted before it, and cc's submit is not in the capture."""
    got = token_trace.first_token_seconds(made_up)
    assert [1e6 * s for s in got] == pytest.approx([950])
    wider = {**made_up, "window": [0.9e-3, 11e-3]}
    assert sorted(1e6 * s for s in token_trace.first_token_seconds(wider)) \
        == pytest.approx([950, 960])


def test_token_metrics_of_the_made_up_trace(made_up, monkeypatch):
    facts = _run_of(made_up, monkeypatch)
    gaps = sorted(1e3 * g for g in token_trace.token_gaps(made_up))
    assert len(gaps) == 14
    assert _reduce("engine.token_gap_p50_ms", facts) == \
        pytest.approx((gaps[6] + gaps[7]) / 2)
    assert gaps[-2] < _reduce("engine.token_gap_p95_ms", facts) < gaps[-1]
    assert _reduce("engine.first_token_p50_ms", facts) == pytest.approx(0.95)
    assert _reduce("engine.compiles_in_window", facts) == 0
    recompiled = token_trace.from_planes(_planes(loop=LOOP + [
        _Ev("llm.compile", 900, 50, program="decode", bucket=4),
        _Ev("llm.compile", 3100, 50, program="decode", bucket=8)]))
    assert _reduce("engine.compiles_in_window",
                   _run_of(recompiled, monkeypatch)) == 1


# ------------------------------------------------------------ the idle time
def _owners(ttrace):
    """Per microsecond of the window: is the device idle, and the labelled
    name of the innermost loop span (None: no span), by brute force."""
    start, end = ttrace["window"]
    n = int(round((end - start) * 1e6))

    def cells(s, e):
        return (max(0, int(round((s - start) * 1e6))),
                max(0, min(n, int(round((e - start) * 1e6)))))

    idle = np.ones(n, bool)
    for _, s, d in next(iter(ttrace["ops"].values())):
        a, b = cells(s, s + d)
        idle[a:b] = False
    owner = np.full(n, None, object)
    for name, s, d in token_trace.labelled(token_trace.loop_spans(ttrace)):
        a, b = cells(s, s + d)      # sorted by start: the innermost is
        owner[a:b] = name           # written last
    return idle, owner


def test_labels_say_the_cause_and_what_lies_inside_a_drain(made_up):
    names = [e[0] for e in
             token_trace.labelled(token_trace.loop_spans(made_up))]
    assert {n for n in names if n.startswith("llm.idle")} == \
        {"llm.idle[empty]", "llm.idle[blocked]"}
    for cause in ("admit", "pressure", "tail"):
        drain = f"llm.decode.drain[{cause}]"
        assert {n for n in names if n.startswith(drain)} == {
            drain, drain + "/llm.decode.pull", drain + "/llm.decode.commit"}
    # a pull and a commit inside a decode step keep their names
    assert names.count("llm.decode.pull") == 2
    assert names.count("llm.decode.commit") == 3


def test_the_loops_line_is_steps_waits_and_the_callers_wrapper(made_up):
    got = token_trace.loop_line_seconds(made_up)
    assert {k: round(1e6 * v) for k, v in got.items()} == {
        "llm.step": 8600, "llm.idle[empty]": 1200, "llm.idle[blocked]": 200,
        "neither": 0}
    # a wrapper round each step shows as what neither span covers
    wrapped = token_trace.from_planes(_planes(loop=[
        _Ev(e.name, e.start_ns // 1000 + 20, e.duration_ns // 1000 - 40,
            **dict(e.stats)) if e.name == "llm.step" else e for e in LOOP]))
    got = token_trace.loop_line_seconds(wrapped)
    assert round(1e6 * got["neither"]) == 13 * 40 - 20
    assert sum(got.values()) == pytest.approx(10e-3)


def test_the_two_idle_shares_sum_to_the_runs_idle_share(made_up, monkeypatch):
    facts = _run_of(made_up, monkeypatch)
    idle, owner = _owners(made_up)
    unoffered = _reduce("device.idle_unoffered_share", facts)
    with_work = _reduce("device.idle_with_work_share", facts)
    assert unoffered == pytest.approx(
        100.0 * (idle & (owner == "llm.idle[empty]")).sum() / idle.size,
        abs=0.02)
    assert unoffered == pytest.approx(100.0 * (500 + 700) / 10000, abs=0.02)
    # what run.py prints of the same window: 1 - busy_s / window_s
    printed = 100.0 * trace.idle_share(
        {"window": made_up["window"], "device": made_up["ops"]})
    assert unoffered + with_work == pytest.approx(printed, abs=1e-9)
    assert printed == pytest.approx(100.0 * idle.sum() / idle.size, abs=0.02)
    # the wait with work pending is with-work
    assert with_work > 100.0 * 200 / 10000


@pytest.mark.parametrize("name,per", [
    ("device.idle_per_prefill_ms", "llm.prefill"),
    ("device.idle_per_chunk_ms", "llm.prefill.chunk")])
def test_a_prompts_arrival_is_charged_its_admit_drain_and_no_other(
        name, per, monkeypatch):
    loop = LOOP if per == "llm.prefill" else LOOP + [
        _Ev("llm.prefill.chunk", s + 10, 30)
        for s in (1550, 2100, 4800, 7650, 10600)] + [
        _Ev("llm.step", 11800, 100), _Ev("llm.prefill.chunk", 11810, 50)]
    ttrace = token_trace.from_planes(_planes(loop=loop))
    idle, owner = _owners(ttrace)
    mine = {"llm.step.admit", "llm.prefill", "llm.prefill.dispatch",
            "llm.prefill.pull", "llm.prefill.scatter", "llm.prefill.commit",
            "llm.prefill.chunk", "llm.decode.drain[admit]",
            "llm.decode.drain[admit]/llm.decode.pull",
            "llm.decode.drain[admit]/llm.decode.commit"}
    want = sum((idle & (owner == label)).sum() for label in mine) / 5
    got = _reduce(name, _run_of(ttrace, monkeypatch))
    assert got == pytest.approx(1e-3 * want, abs=2e-3)
    # the admit drains' idle time is in it (4450-4750; 7400-7650 less the
    # step that still ran), the pressure and tail drains' is not
    inside = [label for label in mine if "drain[admit]" in label]
    assert sum((idle & (owner == label)).sum() for label in inside) == 500
    for other in ("pressure", "tail"):
        label = f"llm.decode.drain[{other}]"
        assert sum((idle & (owner == o)).sum() for o in
                   (label, label + "/llm.decode.pull")) > 0
        assert not any(other in label for label in mine)


def test_no_arrival_in_the_window_no_cost_of_one(made_up, monkeypatch):
    assert _reduce("device.idle_per_chunk_ms",
                   _run_of(made_up, monkeypatch)) is None


# ------------------------------------------------ nothing there to be read
@pytest.mark.parametrize("name", sorted(CELLS) + [UNLISTED])
def test_none_without_the_spans_without_a_trace_and_without_a_capture(
        name, monkeypatch):
    """The parent of the PR that added the spans (its loop's line holds
    neither a wait nor a first token's commit, and its commits name
    nobody), an untraced run, and a capture that is gone."""
    bare = token_trace.from_planes(_planes(loop=[
        _Ev(e.name, e.start_ns // 1000, e.duration_ns // 1000,
            **({} if e.name == "llm.decode.commit" else dict(e.stats)))
        for e in LOOP if e.name not in ("llm.idle", "llm.prefill.commit")]))
    assert not token_trace.covered(bare)
    assert _reduce(name, _run_of(bare, monkeypatch)) is None
    monkeypatch.undo()
    assert _reduce(name, {"trace": None}) is None
    monkeypatch.setattr(program_trace, "SCRATCH", ROOT / "no-such-dir")
    assert _reduce(name, {"trace": {"window": [0, 1]}}) is None


@pytest.mark.parametrize("name", sorted(CELLS) + [UNLISTED])
def test_device_metrics_are_none_where_no_device_was_traced(name,
                                                            monkeypatch):
    """A rehearsal on the CPU has the spans and no device plane: its idle
    time is not a number of the device; the stamps are the program's."""
    cpu = token_trace.from_planes(_planes(busy=[]))
    got = _reduce(name, _run_of(cpu, monkeypatch))
    assert (got is None) == name.startswith("device.")


# ------------------------------------------------------------- the manifest
def test_the_seven_metrics_are_in_the_manifest_for_their_cells():
    bench = manifest.load_manifest()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in CELLS}
    assert sorted(mine) == sorted(CELLS)
    assert not [m for m in bench["per_layer"] if m["name"] == UNLISTED]
    chunks = manifest.metric_spec("per_layer", UNLISTED)
    assert chunks["reducer"] == manifest.metric_spec(
        "per_layer", "device.idle_per_prefill_ms")["reducer"]
    assert chunks["params"]["per"] == "llm.prefill.chunk"
    for name, m in mine.items():
        assert set(CELLS[name]) <= set(m["workloads"])
        assert m["better"] == "lower"
        assert m["moves"] == "serve_out_tokens_per_s"
        device = name.startswith("device.")
        assert m["source"] == ("device_trace" if device else "program_span")
        assert m["layer"] == ("device" if device else "serving engine")
        # what it explains in the two cells that also report a token gap
        spec = manifest.metric_spec("per_layer", name)
        assert "serve_itl_p50_ms" in spec["what"]


def test_rehearsal_prints_the_token_stamps_and_no_device_number():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "falcon-h1-34b.serve-chat-busy", "--seed", "2147484001",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    for name in ("engine.token_gap_p50_ms", "engine.token_gap_p95_ms",
                 "engine.first_token_p50_ms"):
        assert got[f"cpu_rehearsal.{name}"]["value"] > 0, name
    assert got["cpu_rehearsal.engine.token_gap_p50_ms"]["value"] <= \
        got["cpu_rehearsal.engine.token_gap_p95_ms"]["value"]
    assert got["cpu_rehearsal.engine.compiles_in_window"]["value"] >= 0
    assert not [k for k in got if "device." in k]
