"""The ``afmoe`` family (Trinity) in the harness: its configuration file
against the program's preset and the catalog, the manifest's entries BY
NAME, the traffic's grid and rate, the serving job itself on ``afmoe:tiny``
(a ``--rehearse`` of the cell runs the toy GPT-2, which neither keeps pages
of two kinds nor routes nor prefills in chunks, so the family's own model
goes through the job here, at the sizes of ``rehearsal/afmoe.json``: the
window crossed in the prefill and again in the decode steps, blocks given
back), the routed check's faults as ``test_perfbench_routed.py`` lists them,
and the bytes and operations against hand counts.

The tiny model is float32, so its check reads what float32 arithmetic in
another order leaves (under 1e-4 on the CPU), held to 5e-3 here; its
choices are the reference's own save at a rounding.
"""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import bytes_afmoe, flops_afmoe, manifest, traffic
from perfbench.families import afmoe as family

CELL = "trinity-large-preview.serve-mixed-window"
CONFIG = "trinity-large-preview"
TRAFFIC = "serve-mixed-window"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MINE = ("window.decode_attn_ms", "window.decode_full_attn_ms",
        "window.decode_attn_hbm_share", "window.prefill_attn_ms",
        "window.prefill_attn_peak_share", "window.blocks_held_share",
        "attn.prefill_gate_ms", "moe.decode_held_expert_hbm_share",
        "moe.decode_experts_ms", "moe.decode_dispatch_ms",
        "moe.decode_experts_touched", "engine.prefill_chunk_ms")
SHARED = ("engine.ttft_p50_ms", "scheduler.batch_occupancy",
          "scheduler.preemptions", "scheduler.queue_wait_mean_ms",
          # PR 54's token stamps and idle by cause, listed since PR 63
          "engine.token_gap_p50_ms", "engine.token_gap_p95_ms",
          "device.idle_unoffered_share", "device.idle_with_work_share",
          "device.idle_per_prefill_ms", "engine.compiles_in_window",
          "engine.first_token_p50_ms")
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
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
    from ray_tpu.models import afmoe
    config = cell["config_file"]
    preset = afmoe.PRESETS["trinity-large-preview-l5"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] \
        == "afmoe:trinity-large-preview-l5"
    assert config["reduced"] == REDUCED
    assert config["published"]["num_hidden_layers"] == 60
    assert tuple(config["published"]["layer_types"]) \
        == afmoe.PUBLISHED_LAYERS
    assert (config["published"]["num_experts"],
            config["published"]["vocab_size"],
            config["published"]["num_dense_layers"]) == (256, 200192, 6)
    for key in ("deployment", "distorts", "assumed"):
        assert config[key]
    assert "8 chips" in config["deployment"] \
        and "4 sliding layers to 1 full" in config["distorts"]
    # every published width
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts_per_tok"], config["sliding_window"]) \
        == (3072, 12288, 3072, 48, 8, 128, 4, 4096)
    # the cut: layer 0 and layers 8-11, 32 of 256 experts, 1/8 of the rows
    assert config["held_layers"] == [0, 8, 9, 10, 11]
    assert family.held_types(config) == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert (config["num_experts"], config["first_held"],
            config["vocab_size"]) == (32, 0, 200192 // 8)
    assert afmoe.cache_layers(preset) == {"kv": 1, "window": 4, "state": 0}
    assert afmoe.routed_layers(preset) == {"layers": 4, "k": 4,
                                           "held": (0, 32)}
    assert family.routed(config) == {"layers": 4, "k": 4, "experts": 256}
    for said in ("no rotary embedding on the full layers", "the gate's place",
                 "the four norms' places", "expert_bias", "depth-scaled",
                 "1/sqrt(fan_in)"):
        assert any(said in item for item in config["assumed"]), said
    with pytest.raises(ValueError, match="intermediate_size"):
        family.check_sizes({**config, "intermediate_size": 8192}, preset)
    with pytest.raises(ValueError, match="score_func"):
        family.check_sizes({**config, "score_func": "softmax"}, preset)
    with pytest.raises(ValueError, match="published list"):
        family.check_sizes({**config, "held_layers": [0, 7, 8, 9, 10]},
                           preset)
    with pytest.raises(ValueError, match="published list"):
        # a routed layer taken from among the leading dense ones
        family.check_sizes({**config, "held_layers": [0, 1, 2, 4, 3]},
                           preset)
    serve = config["serve"]
    for key in ("logit_atol", "route_margin", "route_differing_share"):
        assert 0 < serve[key] < 1 and "chip" in serve[f"why_{key}"], key
    engine = serve["engine"]
    assert (engine["max_num_seqs"], engine["decode_batch_buckets"],
            engine["block_size"], engine["max_model_len"]) \
        == (16, [16], 64, 26624)
    assert all(b % 2048 == 0 for b in engine["prefill_len_buckets"])
    # the engine's own refusals, here and not on the chip
    from ray_tpu.serve.llm import EngineConfig
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine = {**engine, key: tuple(engine[key])}
    EngineConfig(**engine)


def test_every_number_of_the_catalog_is_in_the_file(cell):
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows
               if r["source_url"] == cell["config_file"]["source"])
    assert row["name"] == "Trinity-Large-Preview"
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
    for name in MINE:
        if name.endswith(("_hbm_share", "_peak_share")):
            assert layer[name]["unit"] == "%" \
                and layer[name]["better"] == "higher"
    assert layer["window.blocks_held_share"]["better"] == "lower"


def test_the_traffic_is_the_issues_grid_under_the_knee(cell):
    spec = cell["traffic_file"]
    assert spec["kind"] == "serve"
    assert spec["prompt_tokens"] == {"median": 8192, "sigma": 0.9,
                                     "lo": 512, "hi": 24576}
    assert spec["output_tokens"] == {"median": 384, "sigma": 0.7,
                                     "lo": 64, "hi": 1536}
    grid = traffic.length_grid(spec)
    assert sorted({p for p, _ in grid}) == [2585, 5110, 8192, 13133, 24576]
    assert sorted({o for _, o in grid}) == [195, 384, 756]
    assert len(grid) == 15
    assert sum(p for p, _ in grid) == 160_788
    assert sum(o for _, o in grid) == 6_675
    # one prompt under the window, four over it: 95% of the prompt tokens
    past = sum(p for p, _ in grid if p > 4096)
    assert 0.95 < past / 160_788 < 0.96
    assert (spec["max_context"], spec["ttft_limit_s"], spec["itl_limit_s"],
            spec["check_prompt_tokens"], spec["check_decode_steps"]) \
        == (26624, 15.0, 0.5, 6144, 8)
    # the cycle divides the 51 s window and the warm-up is whole cycles
    k = 51 / spec["cycle_seconds"]
    assert k == pytest.approx(round(k), abs=1e-9) and round(k) >= 1
    cycles = spec["warm_seconds"] / spec["cycle_seconds"]
    assert cycles == pytest.approx(round(cycles), abs=1e-9) and cycles >= 1
    knee = spec["knee"]
    assert knee["k"] == round(k)
    offered = 6_675 / spec["cycle_seconds"]
    assert knee["offered_tokens_per_s"] == pytest.approx(offered, rel=1e-3)
    assert 0.65 <= offered / knee["knee_tokens_per_s"] <= 0.78
    assert knee["share_of_knee"] == pytest.approx(
        offered / knee["knee_tokens_per_s"], abs=5e-3)
    # a k one larger would offer more than 0.78 of the knee
    assert 6_675 * (round(k) + 1) / 51 > 0.78 * knee["knee_tokens_per_s"]
    # the engine's buckets cover the grid and the check
    buckets = cell["config_file"]["serve"]["engine"]["prefill_len_buckets"]
    for p in [p for p, _ in grid] + [spec["check_prompt_tokens"]]:
        assert any(p <= b for b in buckets)


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
    """The job's context as run.prepare builds it, for afmoe:tiny."""
    from ray_tpu.models import afmoe
    tiny = afmoe.PRESETS["tiny"]()
    toy = json.loads((manifest.BENCH_DIR / "rehearsal" / "afmoe.json")
                     .read_text())
    sizes = family.sizes_of_model(tiny)
    config = {"family": "afmoe", **family.FIXED,
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
    tokens in chunks of 32 (a window of 48: the ring turns), the paged
    decode under the window, blocks given back in both."""
    from perfbench.jobs import serve
    from ray_tpu.util import metrics
    facts = serve.run(_tiny_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    assert facts["preemptions"] == 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    # the check's prompt is 120 tokens and 20 steps: 4 routed layers
    assert notes["route_decisions"] == 4 * (120 + 20)
    assert notes["route_worst_margin"] <= notes["route_margin"]
    assert set(facts["compared"]) >= {"route_worst_margin",
                                      "route_differing"}
    # the window layers held a share of what one table would, and blocks
    # went back (the catalog's counters, which the metric reads)
    snap = metrics.registry_snapshot()

    def total(name):
        return sum(s["value"] for s in snap[name]["series"])
    held = total("rtpu_llm_kv_window_blocks_held")
    unwindowed = total("rtpu_llm_kv_window_blocks_unwindowed")
    assert 0 < held < unwindowed
    assert total("rtpu_llm_kv_window_blocks_released_total") > 0
    spec = manifest.metric_spec("per_layer", "window.blocks_held_share")
    share = manifest.reducer(spec["reducer"])(facts, spec["params"])
    assert share == pytest.approx(100.0 * held / unwindowed)
    assert 0 < share < 100


def _checked(ctx, seed, params=None, reference_params=None):
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


def _far_expert(monkeypatch, pick):
    """Row 2's last pick replaced by ``pick(scores + bias of row 2)``,
    computed with and reported."""
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
        idx = idx.at[2, -1].set(pick(scores[2] + select_bias)
                                .astype(idx.dtype))
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, weight_scale * chosen / (chosen.sum(-1, keepdims=True)
                                             + eps)

    monkeypatch.setattr(moe, "route_sigmoid", far)


def test_the_check_fails_on_a_far_expert(monkeypatch):
    """An expert taken from the bottom of the scores, held or absent: not a
    rounding, and the margin says so."""
    import jax.numpy as jnp
    _far_expert(monkeypatch, jnp.argmin)
    check = _checked(_tiny_ctx(seed=3), 3)
    assert not check["ok"], check
    assert check["route_worst_margin"] > 100 * check["route_margin"]


def test_the_check_fails_on_renormalised_weights(monkeypatch):
    """The chosen weights scaled by 1 and not by route_scale: the first
    routed layer's choice is the reference's, its output is not."""
    from ray_tpu.ops import moe
    route_sigmoid = moe.route_sigmoid
    monkeypatch.setattr(
        moe, "route_sigmoid",
        lambda x, w, b, k, scale, eps=1e-20: route_sigmoid(x, w, b, k, 1.0,
                                                           eps))
    check = _checked(_tiny_ctx(seed=3), 3)
    assert not check["ok"], check
    assert max(check["prefill_logit_diff"], check["decode_logit_diff"]) \
        > 3 * check["logit_atol"]


def test_the_check_fails_on_another_share_of_the_experts(monkeypatch):
    """The program holding experts 4-7 where the file says 0-3: the same
    choices, another chip's part of the sum."""
    from ray_tpu.models import afmoe
    tiny = afmoe.PRESETS["tiny"]
    ctx = _tiny_ctx(seed=3)
    monkeypatch.setitem(afmoe.PRESETS, "tiny", lambda: tiny(first_held=4))
    ctx["config_file"]["first_held"] = 4
    sound = _checked(ctx, 3)
    assert sound["ok"], sound             # the reference follows the file
    monkeypatch.setattr(family, "check_sizes", lambda *a: None)
    ctx["config_file"]["first_held"] = 0
    assert not _checked(ctx, 3)["ok"]


def test_the_check_fails_on_float8_weights_in_the_reference():
    """The rule's control: the reference with every matrix in float8_e4m3,
    the precision below the one served, fails by the logits."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import afmoe

    def fp8(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w if any(
                getattr(k, "key", None) in afmoe.WIDE_PARAMS for k in path)
            else w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)

    check = _checked(_tiny_ctx(seed=3), 3, reference_params=fp8)
    assert not check["ok"], check
    assert max(check["prefill_logit_diff"], check["decode_logit_diff"]) \
        > 3 * check["logit_atol"]


@pytest.mark.parametrize("key", ["route_margin", "route_differing_share"])
def test_a_routed_configuration_without_its_limits_is_refused(key):
    from perfbench.jobs import serve
    ctx = _tiny_ctx(seed=1)
    del ctx["config_file"]["serve"][key]
    with pytest.raises(ValueError, match=key):
        serve.Served(ctx)


# ------------------------------------------------------ bytes and operations
def test_the_bytes_and_operations_against_hand_counts(cell):
    config = cell["config_file"]
    E = 3072
    attention = 3 * E * 6144 + 2 * E * 1024
    assert bytes_afmoe.attention_params(config) == attention == 62_914_560
    assert bytes_afmoe.expert_params(config) == 3 * E * E == 28_311_552
    assert bytes_afmoe.expert_bytes(config) == 56_623_104
    assert bytes_afmoe.dense_layer_params(config) \
        == attention + 3 * E * 12288 == 176_160_768
    assert bytes_afmoe.routed_layer_params(config) \
        == attention + E * 256 + 33 * 3 * E * E == 997_982_208
    # ISSUE 52: 4,321.8 M parameters, 8.64e9 B in bf16
    total = bytes_afmoe.total_params(config)
    assert total == 176_160_768 + 4 * 997_982_208 + 2 * 25024 * E \
        == 4_321_837_056
    assert 2 * total == pytest.approx(8.64e9, rel=1e-3)
    # a decode step's weights outside the experts: 1.24e9 B
    assert bytes_afmoe.decode_fixed_weight_bytes(config) \
        == pytest.approx(1.24e9, rel=2e-3)
    # a position: K and V, 1,024 lanes, float32; a block of 64 of them
    assert bytes_afmoe.position_bytes(config) == 8192
    assert bytes_afmoe.page_bytes(config) == 64 * 8192 == 524_288
    # 8 live rows of 11k: 1.8e9 B of K/V, three fifths the window layers'
    kv = 8 * bytes_afmoe.decode_kv_bytes(config, 11_000)
    assert kv == 8 * 8192 * (11_000 + 4 * 4096) == pytest.approx(1.8e9,
                                                                 rel=3e-3)
    assert 8 * 8192 * 4 * 4096 / kv == pytest.approx(0.6, abs=0.01)
    # the pools: 2.15e9 + 2.18e9 where one table would need 10.7e9
    pools = bytes_afmoe.pool_bytes(config)
    assert pools == {"full": 4096 * 64 * 8192,
                     "window": 4 * 16 * 65 * 64 * 8192,
                     "one_table": 5 * 4096 * 64 * 8192}
    assert pools["full"] + pools["window"] == pytest.approx(4.33e9, rel=2e-3)
    assert pools["one_table"] == pytest.approx(10.7e9, rel=4e-3)
    # a 2,048-chunk at position 12k: 0.4 GFLOP a token of banded attention,
    # 0.3 of global, 1.2 of matmuls
    band = flops_afmoe.chunk_required_attention_flops(config, 12288, 26000,
                                                      2048)
    assert band == 4 * 48 * 128 * 4 * 2048 * 4096
    assert band / 2048 == pytest.approx(0.4e9, rel=0.01)
    full = flops_afmoe.chunk_full_attention_flops(config, 12288, 26000, 2048)
    assert full / 2048 == pytest.approx(0.33e9, rel=0.02)
    assert flops_afmoe.chunk_matmul_flops(config, 2048) / 2048 \
        == pytest.approx(1.2e9, rel=0.01)
    # under the window a query sees what is there; padding asks nothing
    assert flops_afmoe.attended(10, 4096) == 11
    assert flops_afmoe.attended(9000, 4096) == 4096
    assert flops_afmoe.attended(9000) == 9001
    assert flops_afmoe.chunk_required_attention_flops(config, 0, 3, 2048) \
        == 4 * 48 * 128 * 4 * (1 + 2 + 3)
    assert flops_afmoe.chunk_required_attention_flops(config, 4096, 4096,
                                                      2048) == 0


def test_the_window_share_takes_the_blocks_the_steps_were_given(monkeypatch):
    """window.decode_attn_hbm_share: the attribute window_blocks of the
    llm.decode.pull spans inside the window x one block's bytes over the
    kernel's seconds; None where the program has no such attribute (the
    parent)."""
    from types import SimpleNamespace as NS

    from perfbench.reducers import decode_expert_hbm_share, \
        decode_pages_hbm_share
    spec = manifest.metric_spec("per_layer", "window.decode_attn_hbm_share")
    params = spec["params"]
    assert (params["span"], params["attribute"], params["scopes"]) \
        == ("llm.decode.pull", "window_blocks", ["attn_window"])

    def pull(done, **stats):
        return NS(name="llm.decode.pull", start_ns=(done - 0.001) * 1e9,
                  duration_ns=0.001 * 1e9, stats=list(stats.items()))
    planes = [NS(name="/host:CPU", lines=[NS(events=[
        pull(1.5, window_blocks=100), pull(2.5, window_blocks=260),
        pull(9.0, window_blocks=999),            # outside the window
        pull(2.7, experts_touched=3)])])]        # the parent's span
    assert decode_expert_hbm_share.summed(planes, params, (1.0, 3.0)) \
        == (2, 360)
    assert decode_expert_hbm_share.summed(
        planes, {**params, "attribute": "absent"}, (1.0, 3.0)) == (0, 0)
    # with no capture there is nothing to read, and nothing raises
    assert decode_pages_hbm_share.reduce({"trace": None}, params) is None


def test_the_runner_says_what_the_window_layers_read():
    """The attributes of llm.decode.pull, by the kernel's own rule: the
    walk starts at the column of position ctx - window + 1."""
    from ray_tpu.serve.llm.model_runner import ModelRunner
    me = ModelRunner.__new__(ModelRunner)
    me.cfg = type("C", (), {"block_size": 64})()
    me.window_layers, me.window = 4, 4096
    reads = me._window_reads(np.asarray([0, 100, 4095, 4096, 8230]))
    # positions: 0 + 100 + 4095 + 4095 + 4095; blocks: 0 + 2 + 64 + 64 +
    # (129 - 64); one table: 0 + 2 + 64 + 64 + 129
    assert reads == {"window_positions": 4 * 12385,
                     "window_blocks": 4 * 195,
                     "window_blocks_unwindowed": 4 * 259}
