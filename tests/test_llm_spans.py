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
    "llm.prefill.scatter", "llm.prefill.commit", "llm.decode",
    "llm.decode.slots",
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
    return _llm_lines(out), ids, before, after


def _llm_lines(out):
    """The ``llm.*`` events of the capture under ``out``, by thread line:
    (name, start_ns, end_ns, attributes), sorted by start."""
    from jax.profiler import ProfileData
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
    return lines


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


def test_stats_carry_the_blocks_the_decode_steps_contexts_hold(captured):
    """``stats()["attn_blocks_read"]`` and no attribute of ``llm.decode``
    (PR 54: no metric read the span's ``blocks``): a step reads a block or
    more a row, and a scripted run's exact count is held below."""
    lines, _, before, after = captured
    decodes = [e[3] for e in _loop_line(lines) if e[0] == "llm.decode"]
    assert len(decodes) == after["decode_steps"] - before["decode_steps"]
    assert not any("blocks" in d for d in decodes)
    assert after["attn_blocks_read"] - before["attn_blocks_read"] >= \
        sum(int(d["batch"]) for d in decodes) > 0


def test_stats_carry_the_pools_lane_padding(captured):
    """What the pool's device format costs in memory, beside
    ``param_bytes``: N x L x 2 x bs x (F - KV x D) x 4 bytes of lanes
    that pad a position's heads up to whole 128-lane tiles.  A constant of
    the engine: in ``stats()``, and since PR 54 on no span."""
    from ray_tpu.serve.llm.config import resolve_model
    lines, _, before, after = captured
    cfg = small_pool_cfg()
    mcfg = resolve_model(cfg)[1]
    used = mcfg.n_head * mcfg.head_dim
    want = cfg.num_blocks * mcfg.n_layer * 2 * cfg.block_size \
        * (-used % 128) * 4
    assert want > 0 and after["param_bytes"] > 0
    assert before["kv_lane_pad_bytes"] == after["kv_lane_pad_bytes"] == want
    assert not any("kv_lane_pad_bytes" in e[3] for e in _loop_line(lines))


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


# ------------------------------------- the loop's line and the token stamps
IDLE_CAUSES = ("empty", "blocked", "error")
DRAIN_CAUSES = ("sampled", "pressure", "admit", "tail")


def _wait_for(what, limit_s=10.0):
    import time

    from conftest import time_scale
    deadline = time.monotonic() + limit_s * time_scale()
    while not what():
        assert time.monotonic() < deadline, "the engine's loop did not get there"
        time.sleep(0.005)


@pytest.fixture(scope="module")
def loop_run(tmp_path_factory):
    """The engine's own loop thread under a capture, through every wait it
    has and every way a token reaches a stream: (the loop's line, each
    stream's id -> tokens, stats before, stats after).

    GPT-2's tiny preset repeats its prompt's last token for ever, so the
    position embedding is made louder (as tests/test_serve_llm.py does):
    a stop token can then hit in the middle of a run."""
    import jax

    from ray_tpu.serve.llm.config import resolve_model

    cfg = small_pool_cfg()
    mod, mcfg = resolve_model(cfg)
    params = mod.init_params(jax.random.key(cfg.seed), mcfg)
    eng = LLMEngine(cfg, params={**params, "wpe": params["wpe"] * 10})
    out = str(tmp_path_factory.mktemp("loop_capture"))
    prompt = list(range(1, 12))
    tokens = {}

    def finish(streams):
        for s in streams:
            tokens[s.seq_id] = s.tokens()

    try:
        solo = eng.generate(prompt, SamplingParams(max_tokens=20))
        stop_at = max(i for i in range(1, 19) if solo[i] not in solo[:i])
        before = _settled_stats(eng)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=options)
        idles = lambda: eng.stats()["span_s"]["llm.idle"][0]  # noqa: E731
        # blocked: something else holds the pool, the head cannot fit
        eng.cache.alloc_seq("hog", 8 * cfg.block_size)
        n = idles()
        first = eng.submit(prompt, SamplingParams(max_tokens=4))
        _wait_for(lambda: idles() >= n + 3)
        eng.cache.free_seq("hog")
        finish([first])
        # four that outgrow the pool: preemptions, drains under pressure
        # and at the tail
        finish([eng.submit(list(range(1, 12 + i)),
                           SamplingParams(max_tokens=20)) for i in range(4)])
        # a request that arrives while a decode step is in flight
        decode, late = eng.runner.decode, []

        def joined(*args, **kwargs):
            if not late:
                late.append(eng.submit(prompt, SamplingParams(max_tokens=4)))
            return decode(*args, **kwargs)
        eng.runner.decode = joined
        finish([eng.submit(prompt, SamplingParams(max_tokens=8))])
        eng.runner.decode = decode
        finish(late)
        # empty: nothing pending until the next request
        n = idles()
        _wait_for(lambda: idles() >= n + 1)
        # a row that samples beside a greedy one
        finish([eng.submit(prompt, SamplingParams(max_tokens=6,
                                                  temperature=0.8, seed=3)),
                eng.submit(prompt, SamplingParams(max_tokens=6))])
        # a stop token that hits at a decode step's commit, its row in the
        # step enqueued behind: discarded
        finish([eng.submit(prompt, SamplingParams(max_tokens=20,
                                                  stop_token=solo[stop_at])),
                eng.submit(list(range(1, 14)), SamplingParams(max_tokens=24))])
        # error: one step raises
        step = eng.step

        def failing():
            eng.step = step
            raise RuntimeError("a step fails, on purpose")
        eng.step = failing
        finish([eng.submit(prompt, SamplingParams(max_tokens=3))])
        after = _settled_stats(eng)
        n = idles()
        _wait_for(lambda: idles() >= n + 1)      # the last wait closes
        jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    assert len(tokens[list(tokens)[-3]]) == stop_at + 1 < 20
    return _loop_line(_llm_lines(out)), tokens, before, after


def _named(loop, *names):
    return [e for e in loop if e[0] in names]


def test_loop_line_is_steps_and_idles_without_overlap(loop_run):
    loop = loop_run[0]
    line = _named(loop, "llm.step", "llm.idle")
    assert {e[0] for e in line} == {"llm.step", "llm.idle"}
    for a, b in zip(line, line[1:]):
        assert a[2] <= b[1], (a, b)
    # every other span of the loop's thread lies inside a step
    steps = _named(loop, "llm.step")
    for e in loop:
        if e[0] not in ("llm.step", "llm.idle"):
            assert any(s[1] <= e[1] and e[2] <= s[2] for s in steps), e


@pytest.mark.parametrize("cause", IDLE_CAUSES)
def test_idle_spans_say_why_the_loop_waited(loop_run, cause):
    loop = loop_run[0]
    causes = [e[3]["cause"] for e in _named(loop, "llm.idle")]
    assert set(causes) <= set(IDLE_CAUSES) and cause in causes
    waits = [e[2] - e[1] for e in _named(loop, "llm.idle")
             if e[3]["cause"] == cause]
    # a wait ends at its timeout or at a wake, never after
    limit_ns = {"empty": 0.2, "blocked": 0.02, "error": 0.05}[cause] * 1e9
    assert 0 < min(waits) and sorted(waits)[len(waits) // 2] < 3 * limit_ns
    if cause == "error":
        assert len(waits) == 1 and waits[0] >= limit_ns


def test_commits_name_every_token_of_every_stream(loop_run):
    loop, tokens, before, after = loop_run
    got = {sid: 0 for sid in tokens}
    for e in _named(loop, "llm.prefill.commit"):
        got[e[3]["seq"]] += 1
    decode_tokens = 0
    for e in _named(loop, "llm.decode.commit"):
        # (a commit whose rows were all discarded names nobody, and a
        # capture drops an empty attribute)
        members = [m for m in e[3].get("seqs", "").split("|") if m]
        assert len(members) == int(e[3]["tokens"]) == len(set(members))
        decode_tokens += len(members)
        for sid in members:
            got[sid] += 1
    assert got == {sid: len(toks) for sid, toks in tokens.items()}
    firsts = len(_named(loop, "llm.prefill.commit"))
    assert firsts + decode_tokens == \
        after["tokens_out"] - before["tokens_out"] == sum(got.values())
    # a preempted sequence is prefilled again and gets a first token again
    assert firsts == len(tokens) + \
        after["preemptions"] - before["preemptions"] > len(tokens)


def test_a_commit_names_the_step_it_read_and_not_the_rows_it_discarded(
        loop_run):
    loop, _, before, after = loop_run
    batch = {int(e[3]["step"]): e[3] for e in _named(loop, "llm.decode")}
    commits = _named(loop, "llm.decode.commit")
    assert sorted(int(e[3]["step"]) for e in commits) == sorted(batch)
    short = 0
    for e in commits:
        # the pull before it on the line is of the same step
        pull = [p for p in _named(loop, "llm.decode.pull") if p[2] <= e[1]][-1]
        assert int(pull[3]["step"]) == int(e[3]["step"])
        enqueued = batch[int(e[3]["step"])]
        assert set(e[3].get("seqs", "").split("|")) - {""} <= \
            set(enqueued["seqs"].split("|"))
        short += int(enqueued["batch"]) - int(e[3]["tokens"])
    assert short == after["decode_rows_discarded"] \
        - before["decode_rows_discarded"] >= 1


@pytest.mark.parametrize("cause", DRAIN_CAUSES)
def test_commits_inside_a_drain_of_each_cause_are_counted_too(loop_run,
                                                              cause):
    loop, _, before, after = loop_run
    drains = [e for e in _named(loop, "llm.decode.drain")
              if e[3]["cause"] == cause]
    assert len(drains) == after["decode_drains"][cause] \
        - before["decode_drains"][cause] > 0
    for d in drains:
        held = [e for e in _named(loop, "llm.decode.commit")
                if d[1] <= e[1] and e[2] <= d[2]]
        assert len(held) == 1 and "tokens" in held[0][3]


def test_a_first_token_is_committed_after_its_prefill_in_the_same_step(
        loop_run):
    loop = loop_run[0]
    prefills = _named(loop, "llm.prefill")
    for e in _named(loop, "llm.prefill.commit"):
        mine = [p for p in prefills if p[3]["seq"] == e[3]["seq"]
                and p[2] <= e[1]]
        step = next(s for s in _named(loop, "llm.step")
                    if s[1] <= e[1] and e[2] <= s[2])
        assert mine and step[1] <= mine[-1][1]


def test_a_sequences_id_is_never_read_back_as_a_number(loop_run):
    """A capture hands an attribute back as an int or a float where its
    text parses as one (``000123456789``, ``12e345678901``): the spans
    name a sequence by an id that never does."""
    from ray_tpu.serve.llm.engine import _new_seq_id
    ids = {_new_seq_id() for _ in range(2000)}
    assert len(ids) == 2000
    for sid in ids:
        assert len(sid) == 12 and sid.isalnum()
        with pytest.raises(ValueError):
            float(sid)
    loop, tokens = loop_run[0], loop_run[1]
    named = {e[3]["seq"] for e in _named(loop, "llm.prefill.commit")}
    assert named == set(tokens) and all(isinstance(s, str) for s in named)


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
