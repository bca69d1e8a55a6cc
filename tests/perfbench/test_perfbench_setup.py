"""The set-up layer's metrics: ``catalog_sum`` over a synthetic registry,
the six files against BENCHMARK.json, and a traced rehearsal of one
serving cell (perfbench/SETUP_TRACE.md)."""

import pytest

from perfbench import manifest
from perfbench.reducers import catalog_sum
from ray_tpu.util import metrics

from test_perfbench_run import _last_line, _run

SETUP = ("setup.program_s", "setup.trace_s", "setup.lower_s",
         "setup.backend_s", "setup.cache_misses", "setup.programs")
SECONDS, LOOKUPS = "rtpu_xla_compile_seconds", "rtpu_xla_cache_lookups_total"


@pytest.fixture
def registry():
    """A registry that holds what a warm serving run would have told."""
    metrics._reset_for_tests()
    seconds = metrics.Histogram(SECONDS, "", (1.0,), ("stage", "program"))
    for stage, program, value in [
            ("total", "llm.decode", 3.0), ("total", "llm.weights", 0.5),
            ("trace", "llm.decode", 1.0), ("trace", "llm.decode", 0.25),
            ("trace", "other", 7.0), ("backend", "llm.decode", 0.5)]:
        seconds.observe(value, tags={"stage": stage, "program": program})
    lookups = metrics.Counter(LOOKUPS, "", ("result", "program"))
    lookups.inc(4, tags={"result": "hit", "program": "llm.decode"})
    lookups.inc(2, tags={"result": "miss", "program": "other"})
    yield
    metrics._reset_for_tests()


OTHER = {"program": "other"}
TRACED = {"trace": {"window": (0.0, 1.0)}}     # a job's facts after a capture


@pytest.mark.parametrize("params, want", [
    ({"series": SECONDS, "where": {"stage": "total"}, "without": OTHER}, 3.5),
    ({"series": SECONDS, "where": {"stage": "trace"}, "without": OTHER}, 1.25),
    ({"series": SECONDS, "where": {"stage": "trace"}}, 8.25),
    ({"series": SECONDS, "where": {"stage": "trace"}, "scale": 1e3}, 8250.0),
    # some series and none that matches: 0, a warm run's misses
    ({"series": SECONDS, "where": {"stage": "lower"}, "without": OTHER}, 0.0),
    ({"series": LOOKUPS, "where": {"result": "miss"}, "without": OTHER}, 0.0),
    ({"series": LOOKUPS, "where": {}, "without": OTHER}, 4.0),
    ({"series": LOOKUPS}, 6.0),
    # no series of that name at all: nothing to read
    ({"series": "rtpu_no_such_series", "where": {}}, None),
], ids=["total", "trace", "with-other", "scaled", "no-match-histogram",
        "no-match-counter", "any-result", "bare", "missing"])
def test_catalog_sum_over_a_synthetic_registry(registry, params, want):
    assert catalog_sum.reduce(TRACED, params) == want


@pytest.mark.parametrize("facts", [{}, {"trace": None}],
                         ids=["no-key", "no-capture"])
def test_catalog_sum_reads_nothing_from_a_run_without_a_capture(
        registry, facts):
    """A per-layer reading belongs to the traced run's line: a job called
    without a capture (``--trace 0``; the kanana and qwen3_next tests'
    in-process run) gets nothing, whatever the process has compiled."""
    assert catalog_sum.reduce(facts, {"series": SECONDS}) is None
    assert catalog_sum.reduce(facts, {"series": LOOKUPS}) is None


def test_catalog_sum_reads_nothing_from_a_name_without_series():
    metrics._reset_for_tests()
    try:
        metrics.Counter(LOOKUPS, "", ("result", "program"))
        assert catalog_sum.reduce(TRACED, {"series": LOOKUPS}) is None
    finally:
        metrics._reset_for_tests()


@pytest.mark.parametrize("name", SETUP)
def test_a_setup_metric_agrees_with_its_manifest_entry(name):
    bench = manifest.load_manifest()
    entry = manifest.find(bench["per_layer"], name, "metric")
    spec = manifest.metric_spec("per_layer", name)
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": spec["unit"], "better": "lower",
                     "source": "program_counter", "layer": "set-up",
                     "moves": "setup_s"}
    # every cell reports setup_s, and the accepted tests hold that every
    # per-layer entry lists its cells: the six list the 13 there were
    assert set(cells) <= {w["name"] for w in bench["workloads"]}
    assert len(set(cells)) == len(cells) >= 13
    assert spec["reducer"] == "catalog_sum"
    assert spec["params"]["without"] == {"program": "other"}
    from ray_tpu.util.metrics_catalog import CATALOG
    series = CATALOG[spec["params"]["series"]]
    assert set(spec["params"]["where"]) <= set(series["tag_keys"])
    assert spec["unit"] == ("s" if series["kind"] == "histogram" else "count")


@pytest.mark.parametrize("name", SETUP)
def test_a_setup_metric_reads_nothing_where_the_program_tells_nothing(name):
    """The parent of PR 71 under these files: no such series in the
    registry, so the line leaves the metric out and nothing raises."""
    metrics._reset_for_tests()
    spec = manifest.metric_spec("per_layer", name)
    assert manifest.reducer(spec["reducer"])(TRACED, spec["params"]) is None


def test_the_set_up_layer_holds_the_six_and_each_lists_the_same_cells():
    """Present, not pinned to a place or a count: a later PR appends its
    own metrics, under ``setup_s`` too (test_perfbench_manifest.py holds
    the cap)."""
    bench = manifest.load_manifest()
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["moves"] == "setup_s"}
    assert set(SETUP) <= set(mine)
    assert all(mine[name]["workloads"] == mine[SETUP[0]]["workloads"]
               for name in SETUP)


def test_a_traced_rehearsal_prints_the_four_seconds_and_no_count():
    """One serving cell; tests/test_setup_tracing.py holds what a train
    program tells the two series."""
    out = _last_line(_run("--workload", "gpt2-xl-1558m.serve-chat-steady",
                          "--seed", "11", "--seconds", "2", "--trace", "1",
                          "--rehearse"))
    got = {name.removeprefix("cpu_rehearsal."): m for name, m
           in out["metrics"].items() if ".setup." in name}
    # a rehearsal turns the compile cache off: no lookup is made, so the
    # two counts have nothing to read (None, not 0) and are left out
    assert set(got) == set(SETUP[:4])
    assert all(m["unit"] == "s" and m["value"] > 0 for m in got.values())
    parts = sum(got[name]["value"] for name in SETUP[1:4])
    assert parts <= got["setup.program_s"]["value"]
    assert out["correct"] is True
