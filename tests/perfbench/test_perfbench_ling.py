"""The ``ling`` family (Ling-3.0-flash-VL's language model) in the harness:
its configuration file against the program's preset and the catalog, the
manifest's entries BY NAME, the traffic's grid and offer, the serving job
itself on ``ling:tiny`` (a ``--rehearse`` of the cell runs the toy GPT-2,
which keeps neither rows of state nor latent pages, so the family's own
model goes through the job here, at the sizes of ``rehearsal/ling.json``),
the routed check's faults under the group limit, and the bytes and
operations against hand counts.

The tiny model is float32, so its check reads what float32 arithmetic in
another order leaves (under 1e-4 on the CPU), held to 5e-3 here; its
choices are the reference's own save at a rounding.
"""

import json
import time
from pathlib import Path

import pytest

from perfbench import bytes_ling, bytes_ling_rows, flops_ling, manifest, \
    traffic
from perfbench.families import ling as family

CELL = "ling-3.0-flash-vl.serve-reason-latent"
CONFIG = "ling-3.0-flash-vl"
TRAFFIC = "serve-reason-latent"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MINE = ("kda.decode_state_ms", "kda.decode_state_hbm_share",
        "kda.prefill_chunk_ms", "kda.prefill_chunk_peak_share",
        "mla.decode_latent_ms", "mla.decode_latent_hbm_share",
        "mla.prefill_attn_ms", "moe.decode_experts_ms",
        "moe.group_decode_expert_hbm_share",
        "moe.decode_experts_touched", "engine.prefill_chunk_ms")
SHARED = ("engine.ttft_p50_ms", "scheduler.batch_occupancy",
          "scheduler.preemptions", "scheduler.queue_wait_mean_ms",
          # PR 54's token stamps and idle by cause, listed since PR 63
          "engine.token_gap_p50_ms", "engine.token_gap_p95_ms",
          "device.idle_unoffered_share", "device.idle_with_work_share",
          "device.idle_per_prefill_ms", "engine.compiles_in_window",
          "engine.first_token_p50_ms")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]
LIMITS = {"logit_atol": 5e-3, "why_logit_atol": "float32 in another order",
          "route_margin": 1e-4,
          "why_route_margin": "float32 scores in another order",
          "route_differing_share": 0.02,
          "why_route_differing_share": "a rounding apart at most"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


# ------------------------------------------------ the files and the manifest
def test_the_configuration_is_the_programs_preset(cell):
    from ray_tpu.models import ling
    config = cell["config_file"]
    preset = ling.PRESETS["ling-flash-l7"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] == "ling:ling-flash-l7"
    assert config["reduced"] == REDUCED
    assert config["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    for key in ("deployment", "distorts", "assumed"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"] \
        and "one routing group" in config["deployment"] \
        and "eight chips' rows" in config["distorts"]
    # every published width
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_attention_heads"],
            config["head_dim"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["num_experts_per_tok"],
            config["moe_shared_expert_intermediate_size"]) \
        == (2560, 6144, 768, 32, 128, 512, 128, 64, 128, 8, 768)
    # the cut: published layers 1-7, one group of the experts, 1/8 of rows
    assert config["held_layers"] == [1, 2, 3, 4, 5, 6, 7]
    assert family.held_mixers(config) == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert tuple(family.held_mixers(config)) \
        == ling.published_mixers()[1:8] == preset.mixer_types
    assert ling.published_mixers().count("mla") == 7
    assert (config["num_experts"], config["first_held"],
            config["vocab_size"]) == (512 // 8, 0, 157184 // 8)
    assert ling.cache_layers(preset) == {"kv": 0, "latent": 1, "state": 6}
    assert ling.routed_layers(preset) == {"layers": 6, "k": 8,
                                          "held": (0, 64)}
    assert family.routed(config) == {"layers": 6, "k": 8, "experts": 512}
    for said in ("layer_group_size 6", "num_kv_heads_for_linear_attn 0",
                 "kda_safe_gate", "head_wise", "interleaved", "use_qk_norm",
                 "vision tower", "multi-token prediction", "swiglu_limit",
                 "1/sqrt(fan_in)"):
        assert any(said in item for item in config["assumed"]), said
    with pytest.raises(ValueError, match="intermediate_size"):
        family.check_sizes({**config, "intermediate_size": 8192}, preset)
    with pytest.raises(ValueError, match="score_function"):
        family.check_sizes({**config, "score_function": "softmax"}, preset)
    with pytest.raises(ValueError, match="held_layers"):
        # a routed layer taken from among the leading dense ones
        family.check_sizes({**config, "held_layers": [0, 1, 2, 3, 4, 5, 6]},
                           preset)
    with pytest.raises(ValueError, match="SwiGLU limit"):
        family.check_sizes(
            {**config, "held_layers": [1, 35, 36, 37, 38, 39, 40]}, preset)
    with pytest.raises(ValueError, match="mixer_types"):
        family.check_sizes({**config, "held_layers": [1, 3, 4, 5, 6, 7, 8]},
                           preset)
    with pytest.raises(NotImplementedError, match="SwiGLU limit"):
        ling.LingConfig(swiglu_limits=(0.0,) * 41 + (4.0,))
    serve = config["serve"]
    for key in ("logit_atol", "route_margin", "route_differing_share"):
        assert 0 < serve[key] < 1 and "chip" in serve[f"why_{key}"], key
    engine = serve["engine"]
    assert (engine["max_num_seqs"], engine["decode_batch_buckets"],
            engine["block_size"], engine["max_model_len"]) \
        == (64, [64], 64, 15360)
    assert all(b % 2048 == 0 for b in engine["prefill_len_buckets"])
    # the engine's own refusals, here and not on the chip
    from ray_tpu.serve.llm import EngineConfig
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine = {**engine, key: tuple(engine[key])}
    ecfg = EngineConfig(**engine)
    assert ecfg.prefill_len_buckets[-1] >= ecfg.max_model_len


def test_every_number_of_the_catalog_is_in_the_file(cell):
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows
               if r["source_url"] == cell["config_file"]["source"])
    assert row["name"] == "Ling-3.0-flash-VL"
    differ = {k for k, v in row["config"].items()
              if cell["config_file"].get(k, "absent") != v}
    assert differ == set(REDUCED)
    assert all(cell["config_file"]["published"][k] == row["config"][k]
               for k in REDUCED)
    # no width among the reduced
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_manifest_has_the_configuration_the_cell_and_the_metrics():
    bench = manifest.load_manifest()
    entry = manifest.find(bench["configs"], CONFIG, "config")
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    assert entry["source"] == json.loads(
        (manifest.ROOT / entry["file"]).read_text())["source"]
    mine = manifest.find(bench["workloads"], CELL, "workload")
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(mine["why"]) <= 200 and len(entry["why"]) <= 200
    assert len([w for w in bench["workloads"]
                if w["config"] == CONFIG]) == 1
    # end to end: the tokens a second and the set-up; no token gap
    reported = {m["name"] for m in
                manifest.metrics_of_cell(bench, "end_to_end", CELL)}
    assert reported == {"serve_out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in
             manifest.metrics_of_cell(bench, "per_layer", CELL)}
    assert set(MINE) | set(SHARED) <= set(layer)
    for name in MINE:
        m = manifest.find(bench["per_layer"], name, "metric")
        assert CELL in m["workloads"] \
            and m["moves"] == "serve_out_tokens_per_s"
        spec = manifest.metric_spec("per_layer", name)
        assert (spec["layer"], spec["unit"], spec["better"],
                spec["source"], spec["moves"]) \
            == (m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        manifest.reducer(spec["reducer"])       # the reducer is there
        if "bytes" in spec["params"]:
            assert spec["params"]["config"] == entry["file"]
    for name in SHARED:
        assert CELL in manifest.find(bench["per_layer"], name,
                                     "metric")["workloads"]
    # a share of a roofline or of a peak is named so and counted in percent
    shares = [n for n in MINE if n.endswith(("_hbm_share", "_peak_share"))]
    assert len(shares) == 4
    for name in shares:
        assert layer[name]["unit"] == "%" \
            and layer[name]["better"] == "higher"


def test_the_traffic_is_the_issues_grid_under_the_knee(cell):
    spec = cell["traffic_file"]
    assert spec["kind"] == "serve"
    assert spec["prompt_tokens"] == {"median": 1536, "sigma": 1.0,
                                     "lo": 256, "hi": 12288}
    assert spec["output_tokens"] == {"median": 768, "sigma": 0.6,
                                     "lo": 256, "hi": 1536}
    grid = traffic.length_grid(spec)
    assert sorted({p for p, _ in grid}) == [426, 909, 1536, 2595, 5533]
    assert sorted({o for _, o in grid}) == [430, 768, 1372]
    assert len(grid) == 15
    assert sum(p for p, _ in grid) == 32_997
    assert sum(o for _, o in grid) == 12_850
    assert (spec["max_context"], spec["ttft_limit_s"], spec["itl_limit_s"],
            spec["check_prompt_tokens"], spec["check_decode_steps"]) \
        == (15360, 15.0, 0.5, 6144, 8)
    # the cycle divides the 51 s window and the warm-up is whole cycles
    k = 51 / spec["cycle_seconds"]
    assert k == pytest.approx(round(k), abs=1e-9) and round(k) >= 1
    cycles = spec["warm_seconds"] / spec["cycle_seconds"]
    assert cycles == pytest.approx(round(cycles), abs=1e-9) and cycles >= 1
    knee = spec["knee"]
    assert knee["k"] == round(k)
    offered = 12_850 / spec["cycle_seconds"]
    assert knee["offered_tokens_per_s"] == pytest.approx(offered, rel=1e-3)
    assert 0.65 <= offered / knee["knee_tokens_per_s"] <= 0.80
    assert knee["share_of_knee"] == pytest.approx(
        offered / knee["knee_tokens_per_s"], abs=5e-3)
    # the engine's buckets cover the grid and the check
    buckets = cell["config_file"]["serve"]["engine"]["prefill_len_buckets"]
    for p in [p for p, _ in grid] + [spec["check_prompt_tokens"]]:
        assert any(p <= b for b in buckets)
    # every seed's cycle offers the grid once and the same tokens
    for seed in (0, 2 ** 31 + 17, 2 ** 32 - 5):
        cycle = traffic.serve_cycle(spec, 19648, seed)
        assert sorted((len(r.prompt), r.max_tokens) for r in cycle) \
            == sorted(grid)
        assert all(0 <= r.due_s < spec["cycle_seconds"] for r in cycle)
        assert max(max(r.prompt) for r in cycle) < 19648


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
def _tiny_ctx(seed: int, **limits) -> dict:
    """The job's context as run.prepare builds it, for ling:tiny."""
    from ray_tpu.models import ling
    tiny = ling.PRESETS["tiny"]()
    toy = json.loads((manifest.BENCH_DIR / "rehearsal" / "ling.json")
                     .read_text())
    sizes = family.sizes_of_model(tiny)
    config = {"family": "ling", **family.FIXED,
              **{k: sizes[k] for k in family.KEYS}, **toy["config"],
              "serve": {"engine": toy["serve_engine"],
                        **{**LIMITS, **limits}}}
    family.check_sizes(config, tiny)
    spec = json.loads((manifest.BENCH_DIR / "traffic" / f"{TRAFFIC}.json")
                      .read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **toy["traffic"]},
            "seed": seed, "seconds": 1.0, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def test_the_serving_job_runs_the_family_and_its_check_passes():
    """Served(ctx) -> the window -> check_logits: prompts of up to 180
    tokens in chunks of 32 with the state carried, decode through state
    rows and latent pages, the choices audited under the group limit."""
    from perfbench.jobs import serve
    from ray_tpu.util import metrics
    facts = serve.run(_tiny_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    assert facts["preemptions"] == 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    # the check's prompt is 120 tokens and 20 steps: 3 routed layers
    assert notes["route_decisions"] == 3 * (120 + 20)
    assert notes["route_worst_margin"] <= notes["route_margin"]
    assert set(facts["compared"]) >= {"route_worst_margin",
                                      "route_differing"}
    snap = metrics.registry_snapshot()

    def total(name):
        return sum(s["value"] for s in snap[name]["series"])
    assert total("rtpu_llm_latent_pages_read") > 0
    spec = manifest.metric_spec("per_layer",
                                "moe.decode_experts_touched")
    touched = manifest.reducer(spec["reducer"])(facts, spec["params"])
    assert 0 <= touched <= 4                # of the 4 held


def _checked(ctx, seed, reference_params=None):
    from perfbench.jobs import serve
    served = serve.Served(ctx)
    try:
        if reference_params:
            low = reference_params(served.params)
            plain = served.fam.reference_logits
            served.fam.reference_logits = \
                lambda params, tokens, config, **kw: plain(
                    low, tokens, config, **kw)
            try:
                return served.check_logits(seed)
            finally:
                served.fam.reference_logits = plain
        return served.check_logits(seed)
    finally:
        served.close()


def test_the_check_fails_on_a_choice_outside_the_best_groups(monkeypatch):
    """A program that forgets the group limit (top-k over all the experts)
    takes experts of groups the reference did not keep: not a rounding,
    and the margin says so."""
    from ray_tpu.ops import moe
    route_sigmoid = moe.route_sigmoid

    def ungrouped(*args, n_group=1, topk_group=1, **kw):
        return route_sigmoid(*args, **kw)

    monkeypatch.setattr(moe, "route_sigmoid", ungrouped)
    check = _checked(_tiny_ctx(seed=3), 3)
    assert not check["ok"]
    assert check["route_worst_margin"] > 10 * check["route_margin"]
    assert check["route_differing"] > 0


def test_the_check_fails_on_float8_weights_in_the_reference():
    """The nearest precision below: the reference computed on weights
    rounded to float8_e4m3 is another model by the cell's limits."""
    import sys
    sys.path.insert(0, str(manifest.ROOT / "benchmarks"))
    try:
        from ling_check import rounded_to_float8
    finally:
        sys.path.pop(0)
    from ray_tpu.models import ling
    check = _checked(
        _tiny_ctx(seed=4), 4,
        reference_params=lambda p: rounded_to_float8(p, ling.WIDE_PARAMS))
    assert not check["ok"]
    assert check["decode_logit_diff"] > 5 * check["logit_atol"]


@pytest.mark.parametrize("key", ["route_margin", "route_differing_share"])
def test_a_routed_configuration_without_its_limits_is_refused(key):
    from perfbench.jobs import serve
    ctx = _tiny_ctx(seed=1)
    del ctx["config_file"]["serve"][key]
    with pytest.raises(ValueError, match=key):
        serve.Served(ctx)


# --------------------------------------------------- bytes and operations
def test_the_bytes_and_operations_against_hand_counts(cell):
    c = cell["config_file"]
    # a KDA mixer: q, k, v, the decay's full-rank projection and W_o at
    # 2,560 x 4,096 each; beta and the gate 2,560 x 32; the conv 4 x
    # 12,288; A_log, dt_bias, the 128-wide norm
    kda = 5 * 2560 * 4096 + 2 * 2560 * 32 + 4 * 12288 + 32 + 4096 + 128
    assert bytes_ling.kda_params(c) == kda == 52_646_048
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 \
        + 4096 * 2560 + 2560 * 32
    assert bytes_ling.mla_params(c) == mla == 31_965_696
    expert = 3 * 2560 * 768
    assert bytes_ling.expert_bytes(c) == 2 * expert == 11_796_480
    dense = kda + 2 * 2560 + 3 * 2560 * 6144
    routed = 2 * 2560 + 2560 * 512 + expert + 64 * expert
    total = dense + 5 * (kda + routed) + (mla + routed) \
        + 2 * 19648 * 2560 + 2560
    assert bytes_ling.total_params(c) == total == 2_803_841_984
    assert bytes_ling.decode_fixed_weight_bytes(c) \
        == 2 * (total - 19648 * 2560 - 6 * 64 * expert)
    # a row of state: 6 layers of S (32 x 128 x 128) and a tail (3 x 12,288)
    row = 6 * (32 * 128 * 128 + 3 * 12288) * 4
    assert bytes_ling.state_bytes_per_row(c) == row == 13_467_648
    assert bytes_ling.decode_state_bytes(c, 64) == 2 * 64 * row
    # what the state's share multiplies a step's LIVE rows by
    assert bytes_ling_rows.page_bytes(c) == 2 * row
    spec = manifest.metric_spec("per_layer", "kda.decode_state_hbm_share")
    assert (spec["params"]["span"], spec["params"]["attribute"]) \
        == ("llm.decode", "state_rows")
    # a latent page: 64 positions of 576 -> 640 lanes, float32
    assert bytes_ling.latent_lanes(c) == 640
    assert bytes_ling.page_bytes(c) == 64 * 640 * 4 == 163_840
    assert bytes_ling.decode_latent_bytes(c, 3000) == 47 * 163_840
    assert bytes_ling.pool_bytes(c) == {"latent": 8192 * 163_840,
                                        "state": 65 * row}
    # the rule's chunk of 64 positions of one head
    per_chunk = 64 * 63 * 128 + 2 * 64 * 63 * 128 + 6 * 64 * 128 * 128 \
        + 2 * 64 * 65 * 128
    assert flops_ling.rule_chunk_flops(c) == per_chunk == 8_904_704
    assert flops_ling.kda_layers(c) == 6
    assert flops_ling.chunk_rule_flops(c, 0, 2048, 2048) \
        == 6 * 32 * 32 * per_chunk
    # a prompt's last chunk: 100 real positions, one whole chunk and 36
    assert flops_ling.chunk_rule_flops(c, 2048, 2148, 2048) \
        == 6 * 32 * (per_chunk + flops_ling.rule_chunk_flops(c, 36))
    assert flops_ling.chunk_rule_flops(c, 4096, 2148, 2048) == 0
    assert flops_ling.chunk_required_attention_flops \
        is flops_ling.chunk_rule_flops
