"""The ``sdar`` family in the harness: its configuration file against the
program's preset and the catalog, the manifest's entries by name, the
serving job itself on ``llama:tiny-sdar`` (a ``--rehearse`` of the cell runs
the toy GPT-2, which steps by tokens, so the family's own model goes
through the job here, at the sizes of ``rehearsal/sdar.json``), the
family's ``check`` reporting what the README's contract asks and failing on
what is not a rounding, and the new reducers on made-up numbers.

The limits of the tiny model's check were set as PERF.md sets a cell's, from
readings on the CPU in bfloat16 (22-token prompt, 3 blocks: a prefill and
13 passes, 784 decisions; seeds 0-7): sound runs read logit differences of
at most 0.109, margins of at most 0.0033 and at most 56 of 784 decisions
differing (7.1%); three times each.  The reference with every matrix in
float8_e4m3 differs by 0.53 or more on every seed (4.8 times the sound
runs' largest, 1.6 times the limit) and reads margins of 0.019-0.073.
"""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import bytes_sdar, manifest
from perfbench.families import sdar as family

CELL = "sdar-30b-a3b-chat.serve-fixedgen-blocks"
CONFIG = "sdar-30b-a3b-chat"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MINE = ("diffusion.passes_per_block", "diffusion.tokens_per_pass",
        "diffusion.block_gap_p50_ms", "diffusion.pass_device_ms",
        "diffusion.blocks_lost", "attn.block_decode_ms",
        "attn.block_decode_hbm_share", "moe.block_decode_expert_hbm_share",
        "attn.block_prefill_ms")
SHARED = ("engine.ttft_p50_ms", "scheduler.batch_occupancy",
          "scheduler.preemptions", "scheduler.queue_wait_mean_ms",
          "moe.decode_experts_ms", "moe.decode_dispatch_ms",
          "moe.decode_experts_touched", "device.idle_unoffered_share",
          "device.idle_with_work_share", "device.idle_per_prefill_ms",
          "engine.compiles_in_window")
LIMITS = {"logit_atol": 0.33, "why_logit_atol": "three times 0.109",
          "route_margin": 0.01, "why_route_margin": "three times 0.0033",
          "route_differing_share": 0.214,
          "why_route_differing_share": "three times 56 of 784"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


# ------------------------------------------------ the files and the manifest
def test_the_configuration_is_the_programs_preset(cell):
    from ray_tpu.models import llama
    config = cell["config_file"]
    preset = llama.PRESETS["sdar-30b-a3b-l6"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] == "llama:sdar-30b-a3b-l6"
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    for key in ("deployment", "distorts", "assumed"):
        assert config[key]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["vocab_size"]) \
        == (2048, 32, 4, 128, 768, 128, 8, 151936)
    assert config["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_static",
        "mask_token_id": 151669}
    assert llama.block_stepping(preset) == {
        "block": 4, "mask_id": 151669, "per_pass": 1}
    with pytest.raises(ValueError, match="remasking_strategy"):
        family.check_sizes({**config, "generation": {
            **config["generation"],
            "remasking_strategy": "low_confidence_dynamic"}}, preset)
    assert family.routed(config) == {"layers": 6, "k": 8, "experts": 128}
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        family.check_sizes({**config, "moe_intermediate_size": 1536}, preset)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.check_sizes({**config, "norm_topk_prob": False}, preset)
    with pytest.raises(ValueError, match="block_length"):
        family.check_sizes({**config, "generation": {
            **config["generation"], "block_length": 8}}, preset)
    serve = config["serve"]
    for key in ("logit_atol", "route_margin", "route_differing_share"):
        assert serve[key] > 0 and "chip" in serve[f"why_{key}"]
    engine = serve["engine"]
    assert (engine["max_num_seqs"], engine["decode_batch_buckets"],
            engine["num_blocks"], engine["block_size"],
            engine["max_model_len"], engine["prefill_len_buckets"]) \
        == (32, [32], 4096, 16, 4096, [512, 1024, 2048, 4096])


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
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == json.loads(
        (manifest.ROOT / entry["file"]).read_text())["source"]
    mine = manifest.find(bench["workloads"], CELL, "workload")
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, "serve-fixedgen-blocks", 1)
    assert len([w for w in bench["workloads"]
                if w["config"] == CONFIG]) == 1
    # no median token gap end to end: a block's tokens reach the stream
    # together, so it would be a median of zeros
    reported = {m["name"] for m in
                manifest.metrics_of_cell(bench, "end_to_end", CELL)}
    assert reported == {"serve_out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in
             manifest.metrics_of_cell(bench, "per_layer", CELL)}
    assert set(layer) >= set(MINE) | set(SHARED)
    assert not {name for name in layer if "token_gap" in name
                or name in ("engine.itl_p95_ms",
                            "engine.first_token_p50_ms")}
    for name in MINE:
        m = manifest.find(bench["per_layer"], name, "metric")
        assert CELL in m["workloads"] \
            and m["moves"] == "serve_out_tokens_per_s"
        spec = manifest.metric_spec("per_layer", name)
        assert (spec["layer"], spec["unit"], spec["better"],
                spec["source"], spec["moves"]) \
            == (m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        manifest.reducer(spec["reducer"])
    for name in SHARED:
        assert CELL in manifest.find(bench["per_layer"], name,
                                     "metric")["workloads"]
    assert all(m["moves"] in reported for m in layer.values())


def test_the_traffic_is_the_issues(cell):
    from perfbench import traffic
    spec = cell["traffic_file"]
    assert spec["kind"] == "serve"
    assert spec["prompt_tokens"] == {"median": 1024, "sigma": 0.8,
                                     "lo": 256, "hi": 3072}
    assert spec["output_tokens"] == {"median": 256, "sigma": 0.0,
                                     "lo": 256, "hi": 256}
    grid = traffic.length_grid(spec)
    assert sorted({p for p, _ in grid}) == [367, 673, 1024, 1558, 2855]
    assert {o for _, o in grid} == {256} and len(grid) == 15
    # four of the five prompt lengths are not whole blocks: a first block
    # with given positions and a cut last block are in the window
    assert sorted(p % 4 for p in {p for p, _ in grid}) == [0, 1, 2, 3, 3]
    assert (spec["warm_seconds"], spec["trace_seconds"],
            spec["ttft_limit_s"], spec["itl_limit_s"],
            spec["check_prompt_tokens"], spec["check_decode_steps"],
            spec["max_context"]) == (15, 10, 5.0, 0.5, 102, 3, 4096)
    knee = spec["knee"]
    # the offer: the most whole cycles of the 15 requests that the 51 s
    # window holds at or under 0.75 of the knee (a window that cuts a cycle
    # spreads the cell's rate over seeds: the file's ``offered`` has the runs)
    window = json.loads((manifest.ROOT / "BENCHMARK.json")
                        .read_text())["run_seconds"]
    cycles = int(window * 0.75 * knee["knee_rps"] / len(grid))
    assert traffic.rate_rps(spec) == pytest.approx(len(grid) * cycles
                                                   / window)
    assert 0.70 * knee["knee_rps"] < traffic.rate_rps(spec) \
        <= 0.75 * knee["knee_rps"]
    assert knee["found"] and "0.75" in knee["offered"]
    engine = cell["config_file"]["serve"]["engine"]
    assert engine["prefill_len_buckets"][-1] >= engine["max_model_len"] \
        == spec["max_context"]


def test_a_shrunk_configuration_is_handed_to_the_gpt2_family(cell):
    """What --rehearse makes of the cell: GPT-2's names present; such a
    model steps by tokens, and the job's own stepping runs it."""
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    shrunk = {**cell["config_file"], **over["config"]}
    assert family.shrunk(shrunk) and not family.shrunk(cell["config_file"])
    assert family.routed(shrunk) is None and family.stepping(shrunk) is None
    assert isinstance(family.stepping(cell["config_file"]),
                      family.BlockStepping)
    from ray_tpu.models import gpt2
    family.check_sizes(shrunk, gpt2.PRESETS["tiny"]())


# --------------------------------------------------- the job on the family
def _tiny_config() -> dict:
    from ray_tpu.models import llama
    tiny = llama.PRESETS["tiny-sdar"]()
    toy = json.loads((manifest.BENCH_DIR / "rehearsal" / "sdar.json")
                     .read_text())
    config = {"family": "sdar", **family.FIXED,
              **{k: getattr(tiny, attr) for k, attr in family.KEYS.items()},
              "generation": {"block_length": tiny.block_length,
                             "denoising_steps": tiny.denoising_steps,
                             "remasking_strategy": "low_confidence_static",
                             "mask_token_id": tiny.mask_token_id},
              "serve": {"engine": toy["serve_engine"], **LIMITS}}
    return config, toy


def _tiny_ctx(seed: int) -> dict:
    """The job's context as run.prepare builds it, for llama:tiny-sdar."""
    config, toy = _tiny_config()
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    spec = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-fixedgen-blocks.json").read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **over["traffic"]["serve"],
                             "check_prompt_tokens":
                                 toy["check_prompt_tokens"],
                             "check_decode_steps": toy["check_decode_steps"]},
            "seed": seed, "seconds": 1.0, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def test_the_serving_job_runs_the_family_and_its_check_passes():
    """Served(ctx) -> the window -> check_logits through the family's own
    stepping: every request ends with exactly max_tokens tokens however
    many a commit gave it, and every pass is under the limits."""
    from perfbench.jobs import serve
    facts = serve.run(_tiny_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0, facts["compared"]
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    assert facts["wrong_length"] == 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    # 2 layers x (20 prefilled positions + passes of 3 blocks over 24, 28,
    # 32 positions: 3 + 5 + 5 of them)
    assert notes["route_decisions"] == 2 * (20 + 3 * 24 + 5 * 28 + 5 * 32)


@pytest.fixture(scope="module")
def served():
    from perfbench.jobs import serve
    one = serve.Served(_tiny_ctx(seed=3))
    yield one
    one.close()


def _prompt(served, seed=3):
    from perfbench import traffic
    n = served.spec["check_prompt_tokens"]
    return [int(t) for t in traffic.rng_for(seed, "serve_check")
            .integers(0, served.config["vocab_size"], n)]


def test_the_check_reports_what_the_contract_asks(served):
    """Two whole blocks at least (three asked), every pass a Compared, mask
    ids in ``fed`` where the pass was fed them, the prefill's last block
    under "prefill", choices at every position of ``fed``."""
    span, mask = 4, served.config["generation"]["mask_token_id"]
    prompt = _prompt(served)
    compared = served.stepping.check(served, prompt, 3)
    assert isinstance(served.stepping, family.BlockStepping)
    whole = len(prompt) // span * span
    first, passes = compared[0], compared[1:]
    assert first["fed"] == prompt[:whole]
    assert [(p, at) for p, at, _ in first["rows"]] \
        == [("prefill", whole - span + j) for j in range(span)]
    # 22 tokens: 2 given, so 2 denoise passes and a commit; then 4 + 1 twice
    assert len(passes) == 3 + 5 + 5
    lengths = [len(one["fed"]) for one in passes]
    assert lengths == [whole + span] * 3 + [whole + 2 * span] * 5 \
        + [whole + 3 * span] * 5
    masks = [sum(t == mask for t in one["fed"][-span:]) for one in passes]
    assert masks == [2, 1, 0, 4, 3, 2, 1, 0, 4, 3, 2, 1, 0]
    for one in passes:
        assert [(p, at) for p, at, _ in one["rows"]] == [
            ("decode", len(one["fed"]) - span + j) for j in range(span)]
        assert one["choices"].shape == (2, len(one["fed"]), 2)
        assert one["fed"][:len(prompt)] == prompt
    # what a commit pass wrote is what the passes after it hold committed
    assert passes[3]["fed"][:whole + span] == passes[2]["fed"]
    assert served._judge(compared)["ok"]


def test_a_neighbours_position_is_failed_by_the_decode_diff(served):
    """The block's logits handed back one position off: every pass's rows
    then hold a sibling's logits, which no rounding explains."""
    compared = served.stepping.check(served, _prompt(served), 3)
    for one in compared[1:]:
        logits = [row[2] for row in one["rows"]]
        one["rows"] = [(p, at, logits[(j + 1) % len(logits)])
                       for j, (p, at, _) in enumerate(one["rows"])]
    check = served._judge(compared)
    assert not check["ok"]
    assert check["decode_logit_diff"] > 3 * check["logit_atol"]
    assert check["prefill_logit_diff"] <= check["logit_atol"]


def test_a_skipped_commit_is_failed_by_the_block_after_it(served,
                                                           monkeypatch):
    """A commit pass that writes nothing: the passes of the NEXT block then
    read pages nobody wrote, and the decode difference says so."""
    runner = served.eng.runner
    decode = runner.decode

    def uncommitted(*args, **kwargs):
        kwargs["commit"] = np.zeros_like(kwargs["commit"])
        return decode(*args, **kwargs)

    served.eng.cache.pool.fill(0)
    monkeypatch.setattr(runner, "decode", uncommitted)
    check = served._judge(served.stepping.check(served, _prompt(served), 3))
    assert not check["ok"]
    assert check["decode_logit_diff"] > 3 * check["logit_atol"]


def test_the_check_fails_on_float8_weights_in_the_reference(served):
    """The rule's control: the reference with every matrix in float8_e4m3,
    the precision below the one served, fails by the logits."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    def fp8(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w if any(
                getattr(k, "key", None) in llama.WIDE_PARAMS for k in path)
            else w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)

    compared = served.stepping.check(served, _prompt(served), 3)
    sound = served.params
    served.params = fp8(sound)
    try:
        check = served._judge(compared)
    finally:
        served.params = sound
    assert not check["ok"], check
    assert max(check["prefill_logit_diff"], check["decode_logit_diff"]) \
        > check["logit_atol"]


# ------------------------------------------------------ bytes and reducers
def test_bytes_of_an_expert_and_of_a_page(cell):
    config = cell["config_file"]
    assert bytes_sdar.expert_bytes(config) == 3 * 2048 * 768 * 2 == 9_437_184
    assert bytes_sdar.routed_layers(config) == 6
    # K and V, 16 positions of 4 x 128 float32 lanes
    assert bytes_sdar.page_bytes(config) == 2 * 16 * 512 * 4 == 65_536


def test_the_new_reducers_on_made_up_numbers(monkeypatch):
    from perfbench.reducers import catalog_counter_ratio
    from ray_tpu.util import metrics
    made = {
        "rtpu_llm_block_passes": {"series": [{"value": 400.0},
                                             {"value": 100.0}]},
        "rtpu_llm_blocks_committed": {"series": [{"value": 100.0}]},
        "rtpu_llm_blocks_lost": {"series": [{"value": 2.0}, {"value": 1.0}]},
        "rtpu_llm_block_tokens": {"series": [{"value": 390.0}]},
    }
    monkeypatch.setattr(metrics, "registry_snapshot", lambda: made)

    def spec(name):
        return manifest.metric_spec("per_layer", name)

    one = spec("diffusion.passes_per_block")
    assert catalog_counter_ratio.reduce({}, one["params"]) == 5.0
    one = spec("diffusion.tokens_per_pass")
    assert catalog_counter_ratio.reduce({}, one["params"]) == 0.78
    one = spec("diffusion.blocks_lost")
    assert catalog_counter_ratio.reduce({}, one["params"]) == 3.0
    # the parent's program has none of the series: nothing, and no raise
    monkeypatch.setattr(metrics, "registry_snapshot", lambda: {})
    for name in ("diffusion.passes_per_block", "diffusion.tokens_per_pass",
                 "diffusion.blocks_lost"):
        one = spec(name)
        assert manifest.reducer(one["reducer"])({}, one["params"]) is None


def test_the_device_reducers_read_nothing_from_an_untraced_run():
    for name in ("diffusion.pass_device_ms", "diffusion.block_gap_p50_ms",
                 "attn.block_decode_ms", "attn.block_decode_hbm_share",
                 "moe.block_decode_expert_hbm_share",
                 "attn.block_prefill_ms"):
        one = manifest.metric_spec("per_layer", name)
        assert manifest.reducer(one["reducer"])({"trace": None},
                                                one["params"]) is None


def test_the_pass_reducer_counts_the_programs_operations_a_pull(monkeypatch):
    from perfbench import op_scopes, program_trace
    from perfbench.reducers import program_ms_per_span
    events = [("llm.decode.32", "fusion.1", 1.0, 0.004, None),
              ("llm.decode.32", "custom-call.2", 1.1, 0.006, None),
              ("llm.prefill.512", "fusion.9", 1.2, 0.050, None),
              ("", "copy.3", 1.3, 0.001, None)]
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: {
        "window": (0.0, 10.0), "events": {"/device:TPU:0": events}})
    monkeypatch.setattr(program_trace, "of_run", lambda facts: {})
    monkeypatch.setattr(program_trace, "loop_spans", lambda ptrace: [
        ("llm.decode.pull", 1.0, 0.5), ("llm.decode.pull", 2.0, 0.5),
        ("llm.decode.pull", 9.9, 0.5), ("llm.decode", 1.0, 0.1)])
    params = manifest.metric_spec(
        "per_layer", "diffusion.pass_device_ms")["params"]
    assert program_ms_per_span.reduce({}, params) == pytest.approx(5.0)
