"""The ``lfm2`` family in the harness: its configuration file against the
program's preset and the catalog, the manifest's entries by name, the
serving job itself on ``lfm2:tiny`` (a ``--rehearse`` of the cell runs the
toy GPT-2, so the family's own model goes through the job here, at the
sizes of ``rehearsal/lfm2.json``), the routed check failing on what is not
a rounding, the bytes of expert weights a step reads, and the new reducer
on made-up events.

The limits of the tiny model's check were set as PERF.md sets a cell's,
from readings on the CPU in bfloat16 (40-token prompt, 6 decode steps: 368
decisions; seeds 0-7): sound runs read logit differences of at most 0.271,
margins of at most 0.025 and at most 14 decisions of 368 differing (3.8%);
three times each.  The reference with every matrix in float8_e4m3 differs
by 1.29 or more (4.8 times the sound runs' largest, 1.6 times the limit);
a far expert has a margin of several tenths.
"""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import bytes_lfm2, manifest
from perfbench.families import lfm2 as family

CELL = "lfm2-24b-a2b.serve-chat-busy-routed"
CONFIG = "lfm2-24b-a2b"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MINE = ("moe.decode_experts_ms", "moe.decode_dispatch_ms",
        "conv.decode_mixer_ms", "moe.decode_experts_touched",
        "moe.decode_expert_hbm_share")
LIMITS = {"logit_atol": 0.81, "why_logit_atol": "three times 0.271",
          "route_margin": 0.075, "why_route_margin": "three times 0.025",
          "route_differing_share": 0.114,
          "why_route_differing_share": "three times 14 of 368"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


# ------------------------------------------------ the files and the manifest
def test_the_configuration_is_the_programs_preset(cell):
    from ray_tpu.models import lfm2
    config = cell["config_file"]
    preset = lfm2.PRESETS["lfm2-24b-a2b-l9"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] == "lfm2:lfm2-24b-a2b-l9"
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_dense_layers": 2}
    for key in ("deployment", "distorts", "assumed"):
        assert config[key]
    # every published width, all the experts, the whole vocabulary
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["vocab_size"]) \
        == (2048, 11776, 1536, 64, 4, 65536)
    assert family.held_types(config) == ["conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    assert family.routed(config) == {"layers": 8, "k": 4, "experts": 64}
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        family.check_sizes({**config, "moe_intermediate_size": 768}, preset)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.check_sizes({**config, "norm_topk_prob": False}, preset)
    with pytest.raises(ValueError, match="layer_types"):
        family.check_sizes({**config, "num_dense_layers": 2}, preset)
    serve = config["serve"]
    for key in ("logit_atol", "route_margin", "route_differing_share"):
        assert serve[key] > 0 and "chip" in serve[f"why_{key}"]
    assert serve["engine"]["max_num_seqs"] == 32
    assert serve["engine"]["decode_batch_buckets"] == [32]
    assert serve["engine"]["num_blocks"] == 2048


def test_every_number_of_the_catalog_is_in_the_file(cell):
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows
               if r["source_url"] == cell["config_file"]["source"])
    differ = {k for k, v in row["config"].items()
              if cell["config_file"].get(k, "absent") != v}
    assert differ == set(cell["config_file"]["reduced"])


def test_the_manifest_has_the_configuration_the_cell_and_the_metrics():
    bench = manifest.load_manifest()
    entry = manifest.find(bench["configs"], CONFIG, "config")
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert entry["source"] == json.loads(
        (manifest.ROOT / entry["file"]).read_text())["source"]
    mine = manifest.find(bench["workloads"], CELL, "workload")
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, "serve-chat-busy-routed", 1)
    assert len([w for w in bench["workloads"]
                if w["config"] == CONFIG]) == 1
    # end to end the cell reports the tokens a second and the set-up, and
    # not the median token gap: below its knee this model's gap is a
    # seed's arrival pattern's (PERF.md sections 4 and 7; the driver's
    # check refused the cell with it for its spread)
    reported = {m["name"] for m in
                manifest.metrics_of_cell(bench, "end_to_end", CELL)}
    assert reported == {"serve_out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in
             manifest.metrics_of_cell(bench, "per_layer", CELL)}
    assert set(MINE) <= set(layer)
    for name in MINE:
        m = manifest.find(bench["per_layer"], name, "metric")
        assert CELL in m["workloads"] \
            and m["moves"] == "serve_out_tokens_per_s"
        spec = manifest.metric_spec("per_layer", name)
        assert (spec["layer"], spec["unit"], spec["better"],
                spec["source"], spec["moves"]) \
            == (m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        manifest.reducer(spec["reducer"])
    # of what the Falcon cell reports, the metrics that move an end-to-end
    # metric this cell reports: a metric lists no cell that lacks the
    # metric it moves
    falcon = manifest.metrics_of_cell(
        bench, "per_layer", "falcon-h1-34b.serve-chat-busy")
    assert {m["name"] for m in falcon if m["moves"] in reported} \
        == set(layer) - set(MINE)
    assert all(m["moves"] in reported for m in layer.values())


def test_the_traffic_is_serve_chat_busys_grid_at_three_quarters_of_the_knee(
        cell):
    from perfbench import traffic
    spec = cell["traffic_file"]
    busy = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-chat-busy.json").read_text())
    for key in ("kind", "prompt_tokens", "prompt_quantiles", "output_tokens",
                "output_quantiles", "max_context", "check_prompt_tokens",
                "check_decode_steps", "trace_seconds", "ttft_limit_s",
                "itl_limit_s"):
        assert spec[key] == busy[key], key
    assert traffic.length_grid(spec) == traffic.length_grid(busy)
    assert len(traffic.length_grid(spec)) == 20
    knee = spec["knee"]
    assert traffic.rate_rps(spec) == pytest.approx(0.75 * knee["knee_rps"],
                                                   rel=2e-3)
    assert knee["found"] and "0.75" in knee["offered"] \
        and spec["why_warm_seconds"]
    # the grid's prompts take the prefill buckets 64-512, as ISSUE 39
    # lists them; the bucket of 1,024 is there because the engine refuses
    # a largest bucket under max_model_len (a preempted sequence is
    # prefilled again with all it holds), and no request of the grid runs it
    engine = cell["config_file"]["serve"]["engine"]
    assert engine["prefill_len_buckets"] == [64, 128, 256, 512, 1024]
    assert engine["prefill_len_buckets"][-1] >= engine["max_model_len"] \
        == spec["max_context"]
    assert max(p for p, _ in traffic.length_grid(spec)) <= 512


def test_a_shrunk_configuration_is_handed_to_the_gpt2_family(cell):
    """What --rehearse makes of the cell: GPT-2's names present."""
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    shrunk = {**cell["config_file"], **over["config"]}
    assert family.shrunk(shrunk) and not family.shrunk(cell["config_file"])
    assert family.routed(shrunk) is None
    from ray_tpu.models import gpt2
    family.check_sizes(shrunk, gpt2.PRESETS["tiny"]())


# --------------------------------------------------- the job on the family
def _tiny_ctx(seed: int) -> dict:
    """The job's context as run.prepare builds it, for lfm2:tiny."""
    from ray_tpu.models import lfm2
    tiny = lfm2.PRESETS["tiny"]()
    toy = json.loads((manifest.BENCH_DIR / "rehearsal" / "lfm2.json")
                     .read_text())
    sizes = family.sizes_of_model(tiny)
    config = {"family": "lfm2", **family.FIXED,
              **{k: sizes[k] for k in family.KEYS},
              "layer_types": list(tiny.layer_types),
              "head_dim": tiny.head_dim,
              "published": {"num_hidden_layers": tiny.n_layer,
                            "num_dense_layers": tiny.n_dense_layer},
              "rope_parameters": {"rope_theta": tiny.rope_theta,
                                  "rope_type": "default"},
              "serve": {"engine": toy["serve_engine"], **LIMITS}}
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    spec = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-chat-busy-routed.json").read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **over["traffic"]["serve"],
                             "check_prompt_tokens":
                                 toy["check_prompt_tokens"],
                             "check_decode_steps": toy["check_decode_steps"]},
            "seed": seed, "seconds": 1.0, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def test_the_serving_job_runs_the_family_and_its_check_passes():
    """Served(ctx) -> the window -> check_logits: the runner offers its
    route_spec and its choices itself, and the check reads them."""
    from perfbench.jobs import serve
    facts = serve.run(_tiny_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    assert notes["route_decisions"] == 8 * (40 + 6)
    assert notes["route_worst_margin"] <= notes["route_margin"]
    assert set(facts["compared"]) >= {"route_worst_margin",
                                      "route_differing"}


def _checked(ctx, seed, params=None):
    from perfbench.jobs import serve
    served = serve.Served(ctx)
    try:
        if params:
            served.params = params(served.params)
        return served.check_logits(seed)
    finally:
        served.close()


def test_the_check_fails_on_a_far_expert(monkeypatch):
    """Row 2's last pick is the expert the router scores lowest, computed
    with and reported: not a rounding, and the margin says so."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe
    route_sigmoid = moe.route_sigmoid

    def far(x, w_router, select_bias, k, weight_scale, eps=1e-20):
        idx, weights = route_sigmoid(x, w_router, select_bias, k,
                                     weight_scale, eps)
        if idx.shape[0] < 3:
            return idx, weights
        scores = jax.nn.sigmoid(jnp.dot(
            x, w_router.astype(x.dtype), preferred_element_type=jnp.float32))
        worst = jnp.argmin(scores[2] + select_bias).astype(idx.dtype)
        idx = idx.at[2, -1].set(worst)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, weight_scale * chosen / (chosen.sum(-1, keepdims=True)
                                             + eps)

    monkeypatch.setattr(moe, "route_sigmoid", far)
    check = _checked(_tiny_ctx(seed=3), 3)
    assert not check["ok"], check
    assert check["route_worst_margin"] > 2 * check["route_margin"]


def test_the_check_fails_on_float8_weights_in_the_reference():
    """The rule's control: the reference with every matrix in float8_e4m3,
    the precision below the one served, fails by the logits."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import lfm2

    def fp8(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w if any(
                getattr(k, "key", None) in lfm2.WIDE_PARAMS for k in path)
            else w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)

    check = _checked(_tiny_ctx(seed=3), 3, params=fp8)
    assert not check["ok"], check
    assert max(check["prefill_logit_diff"], check["decode_logit_diff"]) \
        > 1.5 * check["logit_atol"]


def test_a_routed_configuration_without_its_limits_is_refused():
    from perfbench.jobs import serve
    ctx = _tiny_ctx(seed=1)
    del ctx["config_file"]["serve"]["route_margin"]
    with pytest.raises(ValueError, match="route_margin"):
        serve.Served(ctx)


# ------------------------------------------------------ bytes and reducers
def test_bytes_of_expert_weights_a_step_reads(cell):
    config = cell["config_file"]
    # an expert: W1 and W3 (2,048 x 1,536) and W2 (1,536 x 2,048), bf16
    assert bytes_lfm2.expert_bytes(config) == 3 * 2048 * 1536 * 2 \
        == 18_874_368
    assert bytes_lfm2.routed_layers(config) == 8
    # 32 rows choosing 4 of 64 touch 64 (1 - (60/64)^32) = 55.9 a layer
    touched = 64 * (1 - (60 / 64) ** 32)
    assert touched == pytest.approx(55.9, abs=0.05)
    assert bytes_lfm2.decode_expert_bytes(config, touched) \
        == pytest.approx(8.44e9, rel=2e-3)
    assert bytes_lfm2.decode_expert_bytes(config, 64) == 64 * 8 * 18_874_368


def _joined(runs=3, layers=8):
    """``runs`` runs of a decode program: each an instruction outside the
    scan (once) and, a routed layer, three expert matmuls of 0.4 ms (the
    op map puts two under moe_experts and the last under moe_combine, as
    on the chip) and one router op; and a prefill's expert matmul."""
    def entry(scope):
        return {"scope": f"jit(decode_state_step)/while/body/{scope}",
                "pass": "", "shape": "", "prim": "", "src": "", "path": ""}
    events, t = [], 1.0
    for _ in range(runs):
        events.append(["llm.decode.32", "fusion.1", t, 1e-4,
                       entry("lm_head")])
        for _ in range(layers):
            for i in range(3):
                events.append(["llm.decode.32", f"ragged-dot-none.{i}",
                               t, 4e-4, entry("moe_experts" if i < 2
                                              else "moe_combine")])
                t += 5e-4
            events.append(["llm.decode.32", "fusion.9", t, 1e-4,
                           entry("moe_router")])
        t += 1e-3
    events.append(["llm.prefill.128", "gmm.3", t, 2e-3,
                   entry("moe_experts")])
    return {"window": [1.0, t + 1.0],
            "events": {"/device:TPU:0": sorted(events, key=lambda e: e[2])},
            "modules": {}}


class _Event:
    def __init__(self, name, start_s, dur_s, **stats):
        self.name, self.stats = name, list(stats.items())
        self.start_ns, self.duration_ns = start_s * 1e9, dur_s * 1e9


def _host_planes(pulls):
    """A capture's host plane: ``pulls`` as (end_s, experts_touched)."""
    line = type("L", (), {"name": "python", "events": [
        _Event("llm.decode.pull", end - 1e-3, 1e-3, step=i,
               experts_touched=touched)
        for i, (end, touched) in enumerate(pulls)]
        + [_Event("llm.decode.pull", 1.5, 1e-3, step=99)]})()   # no count
    return [type("P", (), {"name": "/host:CPU", "lines": [line]})(),
            type("P", (), {"name": "/device:TPU:0", "lines": []})()]


def test_the_expert_share_takes_bytes_and_time_over_one_window(monkeypatch):
    import jax

    from perfbench import op_scopes
    from perfbench.reducers import decode_expert_hbm_share
    spec = manifest.metric_spec("per_layer", "moe.decode_expert_hbm_share")
    params = spec["params"]
    assert params["config"] == f"perfbench/configs/{CONFIG}.json"
    assert (params["bytes"], params["span"], params["attribute"]) == (
        "perfbench.bytes_lfm2", "llm.decode.pull", "experts_touched")
    joined = _joined()
    start, end = joined["window"]
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: joined)
    monkeypatch.setattr(jax, "devices", lambda: [
        type("D", (), {"device_kind": "TPU v5 lite"})()])
    # three steps pulled inside the window (400 experts each, over the 8
    # layers), one before it and one after it
    pulls = [(start - 0.5, 512), (start + 0.1, 400), (start + 0.2, 400),
             (start + 0.3, 400), (end + 0.5, 512)]
    planes = _host_planes(pulls)
    assert decode_expert_hbm_share.summed(planes, params, joined["window"]) \
        == (3, 1200)
    monkeypatch.setattr(decode_expert_hbm_share, "attribute_sum",
                        lambda facts, p, w: decode_expert_hbm_share.summed(
                            planes, p, w))
    facts = {"notes": {}}
    share = decode_expert_hbm_share.reduce(facts, params)
    # 1,200 experts x 18,874,368 B over 3 runs x 8 layers x 3 kernels of
    # 0.4 ms: the prefill's kernel is another program's
    assert share == pytest.approx(
        100 * 1200 * 18_874_368 / (3 * 8 * 3 * 4e-4) / 819e9)
    assert 0 < share < 100
    assert facts["notes"]["decode_expert_hbm"]["steps"] == 3
    # nothing to read: no attribute (the parent's program), no op map, no
    # such operation
    monkeypatch.setattr(decode_expert_hbm_share, "attribute_sum",
                        lambda facts, p, w: (0, 0))
    assert decode_expert_hbm_share.reduce({}, params) is None
    monkeypatch.setattr(decode_expert_hbm_share, "attribute_sum",
                        lambda facts, p, w: (3, 1200))
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: None)
    assert decode_expert_hbm_share.reduce({}, params) is None
    bare = {**joined, "events": {"/device:TPU:0": [
        e for e in joined["events"]["/device:TPU:0"]
        if "ragged-dot" not in e[1]]}}
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: bare)
    assert decode_expert_hbm_share.reduce({}, params) is None


def test_the_metrics_tell_the_kernels_from_what_surrounds_them(monkeypatch):
    """The experts' kernels are known by program and instruction name,
    whichever of two scopes the op map's inference put them under; the
    dispatch metric reads its three scopes WITHOUT them."""
    from perfbench import op_scopes, program_trace
    from perfbench.reducers import scope_ms_by_name
    joined = _joined(runs=2)
    start, end = joined["window"]
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: joined)
    monkeypatch.setattr(program_trace, "of_run", lambda facts: {
        "window": joined["window"], "ops": {}, "spans": {"loop#0": [
            ["llm.step", start + 1e-6, end - start - 1.0 - 2e-6],
            ["llm.decode", start + 2e-6, end - start - 1.0 - 4e-6]]}})
    experts = manifest.metric_spec("per_layer", "moe.decode_experts_ms")
    dispatch = manifest.metric_spec("per_layer", "moe.decode_dispatch_ms")
    for spec in (experts, dispatch):
        assert spec["reducer"] == "scope_ms_by_name"
        assert spec["params"]["program"] == spec["params"]["span"] \
            == "llm.decode"
        assert "shapes" not in spec["params"]
    # one span over both runs: 2 x 8 x 3 kernels of 0.4 ms, no prefill's
    # (the span opens 2 us into the first kernel)
    assert scope_ms_by_name.reduce({}, experts["params"]) \
        == pytest.approx(2 * 8 * 3 * 0.4, rel=1e-3)
    # and the router ops alone, 2 x 8 of 0.1 ms
    assert scope_ms_by_name.reduce({}, dispatch["params"]) \
        == pytest.approx(2 * 8 * 0.1)
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: None)
    assert scope_ms_by_name.reduce({}, dispatch["params"]) is None
    conv = manifest.metric_spec("per_layer", "conv.decode_mixer_ms")
    assert conv["reducer"] == "scope_ms_in_program_span"
    assert conv["params"]["scopes"] == ["conv_in", "conv_step", "conv_out"]
