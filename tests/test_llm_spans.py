"""The serving engine measures itself (ISSUE 25): ``llm.*`` hot spans on
the profiler's clock and as running totals, queue wait as a counter, and
the timeline spans' durations from the same measurement.

The names are a contract (PERF.md, section 3): perfbench's per-layer
metrics read them out of a capture.
"""

import glob
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.util import metrics
from ray_tpu.util import tracing

LOOP_SPANS = [
    "llm.step", "llm.step.admit", "llm.step.plan", "llm.step.publish",
    "llm.prefill", "llm.prefill.dispatch", "llm.prefill.pull",
    "llm.prefill.scatter", "llm.decode", "llm.decode.slots",
    "llm.decode.tables", "llm.decode.dispatch", "llm.decode.pull",
    "llm.decode.commit", "llm.decode.drain", "llm.compile", "llm.preempt"]
ALL_SPANS = LOOP_SPANS + ["llm.submit"]
ROOT = Path(__file__).resolve().parent.parent


def small_pool_cfg(**kw):
    """Four sequences that outgrow ten blocks of 8: preemptions happen."""
    base = dict(model="gpt2:tiny", num_blocks=10, block_size=8,
                max_num_seqs=4, max_model_len=64, max_prefill_tokens=32,
                prefill_len_buckets=(16, 32, 64),
                decode_batch_buckets=(1, 2, 4), share_weights=False)
    base.update(kw)
    return EngineConfig(**base)


def _serve(eng, n=4, max_tokens=20):
    streams = [eng.submit(list(range(1, 12 + i)),
                          SamplingParams(max_tokens=max_tokens))
               for i in range(n)]
    tokens = [s.tokens() for s in streams]
    assert all(len(t) == max_tokens for t in tokens)
    return [s.seq_id for s in streams]


def _settled_stats(eng):
    """stats() once the loop has left its last step and its totals stand:
    a stream ends inside the step's commit, before the step's spans close,
    and a span's count and its seconds are two writes of the loop's thread
    that a snapshot can fall between (``llm.step`` counted, its last step's
    seconds not yet added: the children then outweigh their parent).  Two
    snapshots alike with the step closed are the totals."""
    import time

    from conftest import time_scale
    deadline = time.monotonic() + 10 * time_scale()
    last = None
    while time.monotonic() < deadline:
        stats = eng.stats()
        spans = stats["span_s"]
        closed = not stats["running"] and not stats["waiting"] and \
            spans["llm.step"][0] == spans["llm.step.admit"][0]
        if closed and spans == last:
            return stats
        last = spans if closed else None
        time.sleep(0.01)
    raise AssertionError("the engine's loop did not settle")


def _queue_count(model):
    entry = metrics.registry_snapshot().get("rtpu_llm_queue_seconds")
    return sum(s["value"]["count"] for s in (entry or {"series": []})["series"]
               if s["tags"].get("model") == model)


# ------------------------------------------------- under a profiler capture
@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One engine run under a jax.profiler capture on the CPU backend:
    (events by thread line, submitted ids, stats before, stats after)."""
    import jax
    from jax.profiler import ProfileData

    out = str(tmp_path_factory.mktemp("capture"))
    eng = LLMEngine(small_pool_cfg())
    try:
        before = eng.stats()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=options)
        ids = _serve(eng)
        after = _settled_stats(eng)
        jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    path = sorted(glob.glob(out + "/plugins/profile/*/*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for e in ln.events if e.name.startswith("llm.")]
            if evs:
                lines.append(sorted(evs, key=lambda e: e[1]))
    return lines, ids, before, after


def _loop_line(lines):
    return next(ln for ln in lines if any(e[0] == "llm.step" for e in ln))


@pytest.mark.parametrize("name", LOOP_SPANS)
def test_capture_holds_the_span_on_the_loop_thread(captured, name):
    lines = captured[0]
    assert any(e[0] == name for e in _loop_line(lines))
    for ln in lines:
        if ln is not _loop_line(lines):
            assert not any(e[0] == name for e in ln)


def test_submit_spans_lie_on_the_callers_thread(captured):
    lines, ids = captured[0], captured[1]
    submits = [e for ln in lines if ln is not _loop_line(lines)
               for e in ln if e[0] == "llm.submit"]
    assert sorted(e[3]["seq"] for e in submits) == sorted(ids)
    assert not any(e[0] == "llm.submit" for e in _loop_line(lines))


def test_spans_nest_by_time_and_count_the_steps(captured):
    lines, _, before, after = captured
    loop = _loop_line(lines)

    def named(name):
        return [e for e in loop if e[0] == name]

    def within(inner, outers):
        return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)

    decodes, steps = named("llm.decode"), named("llm.step")
    assert len(decodes) == after["decode_steps"] - before["decode_steps"] > 0
    assert len(named("llm.prefill")) == \
        after["prefill_steps"] - before["prefill_steps"]
    assert len(named("llm.preempt")) == \
        after["preemptions"] - before["preemptions"] > 0
    for part in ("llm.decode.dispatch", "llm.decode.slots"):
        spans = named(part)
        assert len(spans) == len(decodes)
        assert all(within(s, decodes) for s in spans)
    # every step is pulled and committed once: behind the enqueue of the
    # next, inside that one's llm.decode, or in a drain (which the slots
    # of a decode may hold: cache pressure)
    drains = named("llm.decode.drain")
    for part in ("llm.decode.pull", "llm.decode.commit"):
        spans = named(part)
        assert len(spans) == len(decodes)
        assert all(within(s, decodes + drains) for s in spans)
        assert sum(within(s, drains) for s in spans) == len(drains) > 0
    ahead = sum(int(e[3]["ahead"]) for e in decodes)
    assert ahead == after["decode_steps_ahead"] - before["decode_steps_ahead"]
    assert len(decodes) == ahead + len(drains)
    causes = [e[3]["cause"] for e in drains]
    assert {c: causes.count(c) for c in before["decode_drains"]} == {
        c: n - before["decode_drains"][c]
        for c, n in after["decode_drains"].items()}
    assert causes.count("pressure") > 0 and causes.count("tail") > 0
    # a pull names the step it pulls, the decode span the step it enqueued
    assert sorted(int(e[3]["step"]) for e in named("llm.decode.pull")) == \
        sorted(int(e[3]["step"]) for e in decodes)
    for pull in named("llm.decode.pull"):
        holder = next((d for d in decodes if within(pull, [d])), None)
        if holder is not None and not any(
                within(pull, [d]) for d in drains):
            assert int(pull[3]["step"]) == int(holder[3]["step"]) - 1
            assert int(holder[3]["ahead"]) == 1
    for part in ("llm.decode", "llm.decode.drain", "llm.prefill",
                 "llm.step.admit", "llm.step.plan", "llm.step.publish"):
        assert all(within(s, steps) for s in named(part))
    for part in ("llm.prefill.dispatch", "llm.prefill.pull",
                 "llm.prefill.scatter"):
        assert all(within(s, named("llm.prefill")) for s in named(part))
    assert all(within(s, named("llm.decode.slots"))
               for s in named("llm.preempt"))
    # the first call of a (program, bucket) is a compile, once
    compiles = [(e[3]["program"], e[3]["bucket"])
                for e in named("llm.compile")]
    assert len(compiles) == len(set(compiles)) == \
        after["compiles"] - before["compiles"]


def test_spans_of_one_request_share_its_id(captured):
    lines, ids = captured[0], captured[1]
    loop = _loop_line(lines)
    prefills = [e[3] for e in loop if e[0] == "llm.prefill"]
    assert {p["seq"] for p in prefills} == set(ids)
    for p in prefills:
        assert p["queue_ms"] >= 0 and p["tokens"] <= p["bucket"]
    for e in loop:
        if e[0] == "llm.decode":
            members = e[3]["seqs"].split("|")
            assert len(members) == e[3]["batch"] and set(members) <= set(ids)
        if e[0] == "llm.preempt":
            assert e[3]["seq"] in ids and e[3]["ctx"] > 0


def test_decode_spans_carry_the_blocks_their_contexts_hold(captured):
    lines, _, before, after = captured
    blocks = [int(e[3]["blocks"]) for e in _loop_line(lines)
              if e[0] == "llm.decode"]
    assert len(blocks) == after["decode_steps"] - before["decode_steps"]
    assert all(b > 0 for b in blocks)
    assert sum(blocks) == \
        after["attn_blocks_read"] - before["attn_blocks_read"]


def test_decode_spans_and_stats_carry_the_pools_lane_padding(captured):
    """What the pool's device format costs in memory, beside
    ``param_bytes``: N x L x 2 x bs x (F - KV x D) x 4 bytes of lanes
    that pad a position's heads up to whole 128-lane tiles."""
    from ray_tpu.serve.llm.config import resolve_model
    lines, _, before, after = captured
    cfg = small_pool_cfg()
    mcfg = resolve_model(cfg)[1]
    used = mcfg.n_head * mcfg.head_dim
    want = cfg.num_blocks * mcfg.n_layer * 2 * cfg.block_size \
        * (-used % 128) * 4
    assert want > 0 and after["param_bytes"] > 0
    assert before["kv_lane_pad_bytes"] == after["kv_lane_pad_bytes"] == want
    assert {int(e[3]["kv_lane_pad_bytes"]) for e in _loop_line(lines)
            if e[0] == "llm.decode"} == {want}


def test_pull_spans_carry_the_bytes_that_crossed(captured):
    """A greedy run: a decode step's pull is its bucket's ids, a
    prefill's pull one id, and no logits crossed."""
    lines, _, before, after = captured
    loop = _loop_line(lines)
    decodes = [e[3] for e in loop if e[0] == "llm.decode"]
    pulls = [int(e[3]["bytes"]) for e in loop if e[0] == "llm.decode.pull"]
    from ray_tpu.serve.llm.model_runner import _bucket
    buckets = small_pool_cfg().decode_batch_buckets
    assert pulls == [4 * _bucket(int(d["batch"]), buckets) for d in decodes]
    assert {int(e[3]["bytes"]) for e in loop
            if e[0] == "llm.prefill.pull"} == {4}
    assert after["logits_host_bytes"] == before["logits_host_bytes"] == 0
    assert after["sampled_on_host"] == 0
    assert after["sampled_on_device"] == after["tokens_out"]


# -------------------------------------------------------- with no capture
@pytest.fixture(scope="module")
def served():
    """(submitted ids, stats, queue-histogram count added) of one run."""
    cfg = small_pool_cfg(model="gpt2:tiny")
    count0 = _queue_count(cfg.model)
    eng = LLMEngine(cfg)
    try:
        ids = _serve(eng)
        stats = _settled_stats(eng)
    finally:
        eng.shutdown()
    return ids, stats, _queue_count(cfg.model) - count0


@pytest.mark.parametrize("name", ALL_SPANS)
def test_totals_without_a_capture_have_the_span(served, name):
    count, seconds = served[1]["span_s"][name]
    assert count > 0 and seconds > 0


def test_totals_count_the_steps_and_children_fit_their_parent(served):
    _, stats, _ = served
    spans = stats["span_s"]
    assert spans["llm.decode"][0] == stats["decode_steps"]
    assert spans["llm.prefill"][0] == stats["prefill_steps"]
    assert spans["llm.preempt"][0] == stats["preemptions"]
    assert spans["llm.compile"][0] == stats["compiles"]
    assert spans["llm.submit"][0] == len(served[0])
    for part in ("dispatch", "pull", "slots", "commit"):
        assert spans[f"llm.decode.{part}"][0] == stats["decode_steps"]
    assert stats["decode_steps"] == stats["decode_steps_ahead"] \
        + spans["llm.decode.drain"][0]
    assert spans["llm.decode.drain"][0] == \
        sum(stats["decode_drains"].values())
    assert stats["decode_steps_ahead"] > 0
    assert stats["decode_rows_discarded"] == 0
    assert stats["decode_steps"] <= spans["llm.decode.tables"][0] \
        <= 2 * stats["decode_steps"]
    for part in ("admit", "plan", "publish"):
        assert spans[f"llm.step.{part}"][0] == spans["llm.step"][0]
    step_children = ("llm.step.admit", "llm.step.plan", "llm.step.publish",
                     "llm.prefill", "llm.decode")
    assert sum(spans[c][1] for c in step_children) <= spans["llm.step"][1]
    # a step is pulled and committed inside the ``llm.decode`` that enqueues
    # the next one, or inside a drain, and a drain may lie outside every
    # ``llm.decode`` (admit, tail): the two together hold all five children
    decode_children = [f"llm.decode.{p}" for p in
                       ("slots", "tables", "dispatch", "pull", "commit")]
    assert spans["llm.decode.drain"][0] > 0
    assert sum(spans[c][1] for c in decode_children) <= \
        spans["llm.decode"][1] + spans["llm.decode.drain"][1]


def test_queue_wait_counts_first_admissions_apart_from_readmissions(served):
    ids, stats, observed = served
    assert stats["admitted"] == len(ids) == observed
    assert stats["queue_wait_s"] > 0
    assert stats["preemptions"] > 0            # the pool is that small
    assert stats["prefill_steps"] == len(ids) + stats["preemptions"]
    assert stats["requeue_wait_s"] > 0


def test_no_preemption_no_requeue_wait():
    eng = LLMEngine(small_pool_cfg(num_blocks=64))
    try:
        _serve(eng, n=2, max_tokens=4)
        stats = _settled_stats(eng)
    finally:
        eng.shutdown()
    assert stats["preemptions"] == 0 and stats["requeue_wait_s"] == 0.0
    assert stats["admitted"] == 2 and "llm.preempt" not in stats["span_s"]


def test_block_counters_count_what_a_scripted_run_read():
    """Two sequences of known lengths, stepped by hand: the blocks read
    are those their contexts held at each decode step, the table is what
    each step's bucket could name."""
    cfg = small_pool_cfg(num_blocks=64)
    eng = LLMEngine(cfg, start=False)
    prompts, max_tokens, batches = (11, 20), 4, []
    decode = eng.runner.decode
    eng.runner.decode = lambda toks, *a, **kw: batches.append(len(toks)) or \
        decode(toks, *a, **kw)
    try:
        assert eng.stats()["attn_blocks_table"] == 0
        streams = [eng.submit(list(range(1, n + 1)),
                              SamplingParams(max_tokens=max_tokens))
                   for n in prompts]
        while eng.step():
            pass
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert all(len(s.tokens()) == max_tokens for s in streams)
    # the first token comes from the prefill; decode step k of a sequence
    # reads the n + k positions in the pool so far
    bs = cfg.block_size
    assert stats["attn_blocks_read"] == sum(
        -(-(n + k) // bs) for n in prompts for k in range(max_tokens - 1))
    assert stats["decode_steps"] == len(batches) and 2 in batches
    # batches of 1 and 2 are their own buckets of (1, 2, 4)
    assert stats["attn_blocks_table"] == sum(
        cfg.max_blocks_per_seq * b for b in batches)
    assert stats["attn_blocks_read"] < stats["attn_blocks_table"]


def test_a_traced_request_still_yields_timeline_events(monkeypatch):
    """One measurement, two sinks: the cluster timeline's llm.prefill /
    llm.decode_step events carry the hot span's duration, on the wall
    clock's start."""
    import time

    events = []
    monkeypatch.setattr(tracing, "_emit", events.extend)
    eng = LLMEngine(small_pool_cfg(num_blocks=64))
    try:
        t_before = time.time()
        with tracing.trace("request"):
            stream = eng.submit([1, 2, 3, 4, 5], SamplingParams(max_tokens=4))
        stream.tokens()
        spans = _settled_stats(eng)["span_s"]
        t_after = time.time()
    finally:
        eng.shutdown()
    by_name = {}
    for ev in events:
        if ev.get("ph") == "X":
            by_name.setdefault(ev["name"], []).append(ev)
    assert len(by_name["llm.prefill"]) == 1
    assert len(by_name["llm.decode_step"]) == 3
    for ev in by_name["llm.prefill"] + by_name["llm.decode_step"]:
        assert ev["dur"] > 0 and ev["cat"] == "llm"
        assert t_before * 1e6 <= ev["ts"] <= t_after * 1e6
    assert by_name["llm.prefill"][0]["dur"] == \
        pytest.approx(spans["llm.prefill"][1] * 1e6)
    assert sum(ev["dur"] for ev in by_name["llm.decode_step"]) == \
        pytest.approx(spans["llm.decode"][1] * 1e6)


# ------------------------------------------------------------- the primitive
def test_hot_span_opens_no_annotation_and_imports_nothing_without_jax():
    code = textwrap.dedent("""
        import sys
        from ray_tpu.util import tracing
        totals = {}
        with tracing.hot_span("llm.x", totals, seq="a") as span:
            span.set(batch=2)
        with tracing.hot_span("llm.x", totals):
            pass
        assert span._ann is None
        assert totals["llm.x"][0] == 2 and totals["llm.x"][1] > 0
        assert span.dur > 0
        assert "jax" not in sys.modules and "numpy" not in sys.modules
        print("ok")
    """)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60, cwd=ROOT)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


def test_hot_span_totals_are_the_callers_and_survive_an_exception():
    import jax  # noqa: F401 - with jax imported an annotation is opened

    mine, other = {}, {}
    with pytest.raises(ValueError):
        with tracing.hot_span("llm.x", mine) as span:
            raise ValueError("boom")
    assert span._ann is not None
    assert mine["llm.x"][0] == 1 and mine["llm.x"][1] == span.dur > 0
    assert other == {}
