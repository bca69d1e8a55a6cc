"""Set-up seen from inside (util/tracing.py): the process's one listener
on jax's compile events, and the set-up spans that take what it hears.

Events are fed through ``jax.monitoring``'s own ``record_*`` calls, as jax
feeds them, or made by a real jitted call; the readings are the span's
attributes (``seen``), the caller's ``span_s`` and the two catalog series.
"""

import numpy as np
import pytest

from ray_tpu._private import xla_watchdog as xw
from ray_tpu.util import metrics, tracing

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
SECONDS, LOOKUPS = "rtpu_xla_compile_seconds", "rtpu_xla_cache_lookups_total"


@pytest.fixture
def monitoring():
    """jax.monitoring with the listener on it and an empty registry."""
    import jax.monitoring as monitoring
    tracing.listen_to_compiles()
    metrics._reset_for_tests()
    yield monitoring
    metrics._reset_for_tests()


def _told(name: str) -> dict:
    """A catalog series as {(tag values in the tags' order): number}: a
    histogram's sum, a counter's value."""
    entry = metrics.registry_snapshot().get(name, {"series": []})
    return {tuple(v for _, v in sorted(s["tags"].items())):
            s["value"]["sum"] if isinstance(s["value"], dict) else s["value"]
            for s in entry["series"]}


def _stage(monitoring, event: str, seconds: float, inside=()) -> None:
    """One timed stage as jax tells it: its start, what happens inside,
    its duration."""
    monitoring.record_scalar(event, 0.0, fun_name="f")
    for fire in inside:
        fire()
    monitoring.record_event_duration_secs(event, seconds, fun_name="f")


def _compile(monitoring, hit: bool, read_s: float = 0.25,
             backend_s: float = 1.0) -> None:
    """A whole compile of one program that asks the persistent cache."""
    _stage(monitoring, TRACE, 0.5)
    _stage(monitoring, LOWER, 0.125)
    inside = [lambda: monitoring.record_event(LOOKUP)]
    if hit:
        inside += [lambda: monitoring.record_event(HIT),
                   lambda: monitoring.record_event_duration_secs(
                       CACHE_READ, read_s)]
    _stage(monitoring, BACKEND, backend_s, inside)


# ------------------------------------------------------------ the listener
def test_one_listener_however_often_it_is_asked_for(monitoring):
    from jax._src import monitoring as registry
    for _ in range(3):
        tracing.listen_to_compiles()
    mine = tracing._COMPILES
    assert registry.get_event_duration_listeners().count(mine.took) == 1
    assert registry.get_event_listeners().count(mine.said) == 1
    assert registry.get_scalar_listeners().count(mine.started) == 1


@pytest.mark.parametrize("hit", [True, False], ids=["hit", "miss"])
def test_events_inside_a_span_land_under_its_program(monitoring, hit):
    totals = {}
    with tracing.setup_span("llm.compile", totals, "llm.decode",
                            program="decode", bucket=8) as span:
        _compile(monitoring, hit)
    assert span.seen == {
        "trace_s": 0.5, "lower_s": 0.125, "backend_s": 1.0,
        "cache_read_s": 0.25 if hit else 0.0,
        "cache_hits": int(hit), "cache_misses": int(not hit)}
    assert totals["llm.compile"][0] == 1
    assert totals["llm.compile"][1] == span.dur > 0
    seconds = _told(SECONDS)
    assert seconds[("llm.decode", "trace")] == 0.5
    assert seconds[("llm.decode", "lower")] == 0.125
    assert seconds[("llm.decode", "backend")] == 1.0
    assert seconds[("llm.decode", "total")] == span.dur
    assert seconds.get(("llm.decode", "cache_read")) == (
        0.25 if hit else None)
    assert _told(LOOKUPS) == {
        ("llm.decode", "hit" if hit else "miss"): 1.0}


def test_events_outside_every_span_land_under_other(monitoring):
    _compile(monitoring, hit=False)
    seconds = _told(SECONDS)
    assert seconds == {("other", "trace"): 0.5, ("other", "lower"): 0.125,
                       ("other", "backend"): 1.0}      # and no total
    assert _told(LOOKUPS) == {("other", "miss"): 1.0}


def test_a_compile_that_never_asked_the_cache_counts_no_lookup(monitoring):
    with tracing.setup_span("train.compile", {}, "train.step") as span:
        _stage(monitoring, BACKEND, 2.0)
    assert span.seen["backend_s"] == 2.0
    assert span.seen["cache_hits"] == span.seen["cache_misses"] == 0
    assert LOOKUPS not in metrics.registry_snapshot()


def test_nested_spans_charge_the_innermost_and_total_the_outermost(
        monitoring):
    totals = {}
    with tracing.setup_span("llm.compile", totals, "llm.prefill",
                            program="prefill", bucket=64) as outer:
        _stage(monitoring, TRACE, 0.5)
        with tracing.setup_span("llm.compile", totals, "llm.fold",
                                program="fold", bucket=2) as inner:
            _compile(monitoring, hit=False)
        _stage(monitoring, LOWER, 0.25)
    assert inner.seen["trace_s"] == 0.5 and inner.seen["cache_misses"] == 1
    assert outer.seen == {"trace_s": 0.5, "lower_s": 0.25, "backend_s": 0.0,
                          "cache_read_s": 0.0, "cache_hits": 0,
                          "cache_misses": 0}
    seconds = _told(SECONDS)
    assert seconds[("llm.fold", "backend")] == 1.0
    assert seconds[("llm.prefill", "lower")] == 0.25
    # wall time once, under the outermost span's program
    assert seconds[("llm.prefill", "total")] == outer.dur
    assert ("llm.fold", "total") not in seconds
    assert totals["llm.compile"][0] == 2


def test_a_stage_inside_another_is_in_the_outer_ones_seconds(monitoring):
    """A jitted function traced inside a trace, an operation a trace runs
    eagerly: jax times both, the inner inside the outer."""
    with tracing.setup_span("train.compile", {}, "train.step") as span:
        _stage(monitoring, TRACE, 3.0, inside=[
            lambda: _stage(monitoring, TRACE, 1.0),
            lambda: _compile(monitoring, hit=True)])
    assert span.seen["trace_s"] == 3.0
    assert span.seen["lower_s"] == span.seen["backend_s"] == 0.0
    # the inner compile's lookup is a lookup all the same
    assert span.seen["cache_hits"] == 1
    assert _told(SECONDS)[("train.step", "trace")] == 3.0


def test_spans_of_two_threads_do_not_see_each_other(monitoring):
    import threading
    seen = {}
    entered, fired = threading.Event(), threading.Event()

    def other():
        with tracing.setup_span("train.init", {}, "train.init") as span:
            entered.set()
            assert fired.wait(10)
        seen["other"] = span.seen

    thread = threading.Thread(target=other)
    thread.start()
    assert entered.wait(10)
    _compile(monitoring, hit=False)         # this thread has no span open
    fired.set()
    thread.join(10)
    assert not thread.is_alive()
    assert seen["other"]["trace_s"] == 0.0
    assert ("other", "trace") in _told(SECONDS)


def test_a_jitted_functions_first_call_is_seen_and_its_second_is_not(
        monitoring):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.tanh(x) @ x

    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    with tracing.setup_span("train.compile", {}, "train.step") as first:
        f(x)
    assert first.seen["trace_s"] > 0 and first.seen["lower_s"] > 0
    assert first.seen["backend_s"] > 0
    assert first.seen["cache_hits"] + first.seen["cache_misses"] == 1
    assert first.seen["trace_s"] + first.seen["lower_s"] \
        + first.seen["backend_s"] <= first.dur
    with tracing.setup_span("train.compile", {}, "train.step") as second:
        f(x)
    assert second.seen == {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                           "cache_read_s": 0.0, "cache_hits": 0,
                           "cache_misses": 0}


def test_a_persistent_cache_hit_is_a_backend_event_and_a_hit(monitoring):
    """What the watchdog's docstring says of jax 0.9.0: the backend event
    wraps ``compile_or_get_cached``, so a load from the persistent cache
    fires it too."""
    import jax
    import jax.numpy as jnp
    kept = {name: getattr(jax.config, name) for name in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    def made():
        # the same program from another function object: jit's in-memory
        # caches know nothing of it, the persistent cache's key is the same
        return jax.jit(lambda x: jnp.cos(x) * 71.0)

    try:
        x = jnp.ones((3,))
        jax.block_until_ready(x)
        with tracing.setup_span("train.compile", {}, "train.step") as cold:
            made()(x)
        with tracing.setup_span("train.compile", {}, "train.step") as warm:
            made()(x)
    finally:
        for name, value in kept.items():
            jax.config.update(name, value)
    assert (cold.seen["cache_misses"], cold.seen["cache_hits"]) == (1, 0)
    assert (warm.seen["cache_misses"], warm.seen["cache_hits"]) == (0, 1)
    assert warm.seen["backend_s"] >= warm.seen["cache_read_s"] > 0
    lookups = _told(LOOKUPS)            # (the array's own: under other)
    assert lookups[("train.step", "hit")] == 1.0
    assert lookups[("train.step", "miss")] == 1.0


def test_with_built_in_metrics_off_the_span_still_carries_what_it_saw(
        monitoring, monkeypatch):
    from ray_tpu._private.config import GLOBAL_CONFIG
    monkeypatch.setattr(GLOBAL_CONFIG, "metrics_enabled", False)
    with tracing.setup_span("train.compile", {}, "train.step") as span:
        _compile(monitoring, hit=True)
    assert span.seen["backend_s"] == 1.0 and span.seen["cache_hits"] == 1
    assert SECONDS not in metrics.registry_snapshot()
    assert LOOKUPS not in metrics.registry_snapshot()


def test_hot_span_is_what_it_was():
    """The path a step takes after its first call: no set-up span's code."""
    assert tracing.hot_span.__slots__ == ("name", "totals", "dur", "_t0",
                                          "_ann")
    assert "__enter__" in vars(tracing.setup_span)
    totals = {}
    with tracing.hot_span("llm.step", totals):
        assert tracing._COMPILES.spans == []
    assert totals["llm.step"][0] == 1


# ------------------------------------------------- where the spans are put
@pytest.fixture(scope="module")
def built():
    """One tiny runner and one tiny train program for the module, each
    called three times a shape with the watchdog armed; what they left in
    their ``span_s``, the two series and the watchdog's counts."""
    import jax
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm import model_runner as mr
    patch = pytest.MonkeyPatch()
    patch.setenv("RAY_TPU_XLA_WATCHDOG", "1")
    tracing.listen_to_compiles()
    metrics._reset_for_tests()
    xw.reset_xla_stats()
    try:
        runner = mr.ModelRunner(EngineConfig(
            model="gpt2:tiny", num_blocks=64, block_size=8, max_num_seqs=4,
            max_model_len=64, max_prefill_tokens=32,
            prefill_len_buckets=(16, 32, 64), decode_batch_buckets=(1, 2, 4),
            share_weights=False))
        for tokens in ([1, 2, 3, 4, 5], list(range(1, 21))):    # two buckets
            for _ in range(3):
                runner.prefill(tokens)
        cfg = gpt2.tiny()
        prog = spmd.build_train_program(
            loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
            init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
            optimizer=spmd.default_optimizer(lr=1e-2, warmup=1,
                                             total_steps=50),
            mesh_config=MeshConfig(data=8))
        state = prog.init_fn(jax.random.key(0))
        toks = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 33)).astype(np.int32)
        batch = spmd.shard_batch(prog, {"tokens": toks})
        for _ in range(3):
            state, _metrics = prog.step_fn(state, batch)
        yield {"runner": runner.span_s, "train": prog.span_s,
               "seconds": _told(SECONDS), "lookups": _told(LOOKUPS),
               "watchdog": xw.xla_stats()}
    finally:
        patch.undo()
        xw.reset_xla_stats()
        metrics._reset_for_tests()


def test_a_runner_leaves_one_llm_compile_a_program(built):
    span_s, seconds = built["runner"], built["seconds"]
    assert span_s["llm.weights.prepare"][0] == 1
    assert span_s["llm.compile"][0] == 2            # one a bucket
    assert span_s["llm.prefill.dispatch"][0] == 6
    assert seconds[("llm.prefill", "trace")] > 0
    assert seconds[("llm.prefill", "backend")] > 0
    parts = sum(seconds[("llm.prefill", stage)]
                for stage in ("trace", "lower", "backend"))
    assert parts <= seconds[("llm.prefill", "total")] \
        <= span_s["llm.compile"][1] + 1e-9
    assert ("llm.weights", "total") in seconds
    assert sum(v for (program, _), v in built["lookups"].items()
               if program == "llm.prefill") >= 2


def test_an_engine_builds_its_cache_inside_a_span(monitoring):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    eng = LLMEngine(EngineConfig(
        model="gpt2:tiny", num_blocks=16, block_size=8, max_num_seqs=2,
        max_model_len=32, max_prefill_tokens=32, prefill_len_buckets=(32,),
        decode_batch_buckets=(2,), share_weights=False), start=False)
    try:
        assert eng.span_s["llm.cache.build"][0] == 1
        assert eng.cache.pool.nbytes == sum(
            leaf.nbytes for leaf in eng.cache.pool.read(
                lambda held: list(held.values())))
        assert ("llm.cache", "total") in _told(SECONDS)
    finally:
        eng.shutdown()


def test_a_train_program_leaves_one_train_compile_and_one_train_init(built):
    span_s, seconds = built["train"], built["seconds"]
    assert span_s["train.init"][0] == 1
    assert span_s["train.compile"][0] == 1
    for program in ("train.init", "train.step"):
        parts = sum(seconds[(program, stage)]
                    for stage in ("trace", "lower", "backend"))
        assert 0 < parts <= seconds[(program, "total")]
    assert seconds[("train.step", "total")] == span_s["train.compile"][1]
    assert span_s is not built["runner"]        # an owner's own totals


@pytest.mark.parametrize("site, owner, span, programs", [
    ("train.step", "train", "train.compile", 1),
    ("llm.prefill", "runner", "llm.compile", 2)])
def test_the_armed_watchdog_counts_through_the_shared_listener(
        built, site, owner, span, programs):
    # a compile a program, the same ones the spans counted
    assert built["watchdog"][site][0] == built[owner][span][0] == programs
    from jax._src import monitoring as registry
    assert tracing._ON_COMPILE.count(xw._note_compile) == 1
    assert registry.get_event_duration_listeners().count(
        tracing._COMPILES.took) == 1
