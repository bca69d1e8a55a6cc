"""The Qwen3-Next (``qwen3_next``) family file, its operation count, its
nine metrics and its cell: the configuration keeps every published width
and states its share; a file whose block the program does not have is
refused; the training job's own ``run`` agrees with the reference at the
rehearsal's size; the metrics read their scopes and kernels and return
nothing, without raising, where a program has none of them."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops_qwen3_next, manifest, op_scopes
from perfbench.reducers import qwen3_next_peak_share

CELL = "qwen3-next-80b-a3b.train-b2-s8192"
CONFIG = "qwen3-next-80b-a3b"
CONFIG_FILE = manifest.BENCH_DIR / "configs" / f"{CONFIG}.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ("gdn.mixer_ms", "gdn.rule_ms", "gdn.conv_ms",
               "gdn.rule_peak_share", "attn.gated_flash_ms",
               "attn.gated_flash_peak_share", "moe.dispatch_ms",
               "moe.experts_scope_ms",
               "moe.train_share_expert_peak_share")
SHARED_METRICS = ("train_program.step_ms", "train_program.mfu",
                  "kernels.custom_call_ms", "device.train_idle_share",
                  # PR 36's scopes, listed since PR 63
                  "train_program.optimizer_ms", "train_program.head_loss_ms",
                  "train_program.attn_scope_ms", "train_program.unscoped_ms")


def _config():
    return json.loads(CONFIG_FILE.read_text())


@pytest.fixture(scope="module")
def fam():
    return manifest.family("qwen3_next")


def test_the_configuration_keeps_every_published_width(fam):
    config = _config()
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.gdn_key_heads, cfg.gdn_value_heads,
            cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.conv_kernel) == \
        (2048, 16, 32, 128, 128, 4)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.rotary_dim,
            cfg.rope_theta, cfg.attn_interval) == (16, 2, 256, 64, 1e7, 4)
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.expert_dim,
            cfg.shared_dim) == (512, 10, 512, 512)
    assert config["norm_topk_prob"] is True
    assert cfg.rms_eps == 1e-6 and cfg.max_positions == 262144
    assert cfg.rule_chunk == 64
    # the share: what is reduced, and what it was
    assert config["reduced"] == REDUCED
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert (cfg.n_layer, cfg.n_period, cfg.n_held_experts,
            cfg.first_held_expert, cfg.vocab_size) == (4, 1, 64, 0, 18992)
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert cfg.vocab_size * 8 == 151936 and cfg.n_held_experts * 8 == 512
    for key in ("deployment", "distorts", "assumed", "why_reduced"):
        assert config[key]
    assert "attention and the rule see eight chips' tokens for one chip's " \
           "experts" in config["distorts"][0]
    assert cfg.param_dtype == jnp.bfloat16 and cfg.remat_policy == "attn"
    # what the chip holds is what the file's cut says it is
    shapes = jax.eval_shape(lambda r: fam.module().init_params(r, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 1028.3
    assert "1,028.3 M parameters" in config["deployment"]["held"]


def test_every_number_of_the_catalog_is_in_the_file():
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    config = _config()
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows if r["source_url"] == config["source"])
    assert row["name"] == "Qwen3-Next-80B-A3B-Instruct"
    differ = {k for k, v in row["config"].items()
              if config.get(k, "absent") != v}
    assert differ == set(REDUCED)
    assert all(config["published"][k] == row["config"][k] for k in REDUCED)


@pytest.mark.parametrize("change", [
    {"mlp_only_layers": [0]}, {"use_sliding_window": True},
    {"decoder_sparse_step": 2}, {"tie_word_embeddings": True},
    {"norm_topk_prob": False}, {"rope_scaling": {"type": "yarn"}},
    {"model_type": "qwen3_moe"}, {"hidden_act": "gelu"},
    {"attention_bias": True}, {"num_experts": 32}, {"num_hidden_layers": 6},
])
def test_a_block_the_program_does_not_have_is_refused(fam, change):
    config = {**_config(), **change}
    with pytest.raises(ValueError):
        fam.check_sizes(config)
    with pytest.raises(ValueError):
        fam.model_config(config, config["train"]["model_options"])


def test_another_interval_is_fine(fam):
    config = {**_config(), "full_attention_interval": 2}
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.attn_interval, cfg.n_period) == (2, 2)


def test_a_share_that_is_not_the_routers_is_refused(fam):
    for held in (list(range(500, 564)), list(range(0, 128, 2))):
        config = _config()
        config["deployment"] = {**config["deployment"],
                                "held_expert_ids": held}
        with pytest.raises(ValueError):
            fam.check_sizes(config)


def _rehearsal_cell():
    from perfbench.run import _rehearsal_cell
    return _rehearsal_cell(manifest.load_cell(manifest.load_manifest(), CELL))


def test_the_rehearsals_gpt2_named_overrides_shrink_this_model(fam):
    config = _rehearsal_cell()["config_file"]
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.n_layer, cfg.n_period, cfg.n_head,
            cfg.n_kv_head) == (64, 4, 1, 4, 2)
    assert (cfg.vocab_size, cfg.max_positions) == (256, 64)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.gdn_key_heads,
            cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim,
            cfg.expert_dim, cfg.shared_dim) == (8, 2, 2, 4, 8, 8, 16, 16)
    # the router, the share and the choice stay
    assert (cfg.n_routed_experts, cfg.n_held_experts,
            cfg.experts_per_token) == (512, 64, 10)
    assert cfg.remat_policy == "full" and cfg.dtype == jnp.float32


def _ctx(cell):
    return {**cell, "seed": 2 ** 31 + 5, "seconds": 0.2, "trace": False,
            "rehearse": True, "notes": False, "devices": jax.devices()[:1],
            "t_start": time.perf_counter(), "marks": {}, "trace_dir": "",
            "peaks": None}


def test_the_training_jobs_own_run_agrees_with_the_reference(fam):
    """``jobs/train.run`` on this model at the rehearsal's size: the
    program's loss on the check sequences equals the float32 reference's
    to 1e-5 (both float32 here; tests/test_qwen3_next.py shows at decisive
    weights that a wrong convention would be caught)."""
    from perfbench.jobs import train
    cell = _rehearsal_cell()
    facts = train.run(_ctx(cell))
    assert facts["correct"] and all(facts["checks"].values())
    assert facts["notes"]["loss_abs_diff"] < 1e-5
    assert facts["steps"] >= cell["traffic_file"]["min_steps"]
    assert facts["flops_per_token"] == fam.flops_per_token(
        cell["config_file"], cell["traffic_file"]["seq"])
    bench, printed = manifest.load_manifest(), {}
    for group in ("end_to_end", "per_layer"):
        for m in manifest.metrics_of_cell(bench, group, CELL):
            spec = manifest.metric_spec(group, m["name"])
            value = manifest.reducer(spec["reducer"])(facts, spec["params"])
            if value is not None:
                printed[m["name"]] = value
    assert set(printed) == {"train_tokens_per_s_per_chip", "setup_s",
                            "train_program.step_ms"}
    assert all(v > 0 for v in printed.values())


def test_the_traffic_file_is_the_one_that_was_there():
    """The cell runs Kanana's traffic file unchanged: batch 2, 8,192-token
    sequences, a ring of 4, sync_lag 2."""
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    spec = cell["traffic_file"]
    assert (spec["kind"], spec["batch"], spec["seq"], spec["ring"],
            spec["sync_lag"], spec["check_sequences"]) == \
        ("train", 2, 8192, 4, 2, 2)
    assert cell["traffic"] == "train-b2-s8192"


def test_flops_qwen3_next_equals_a_hand_count(fam):
    """The issue's count from the config's keys, by part in GFLOP a token
    at 8,192 positions."""
    sizes = fam.sizes(_config())
    parts = flops_qwen3_next.matmul_params_per_token(sizes)
    assert parts["gdn_projections"] == 3 * (2048 * 12288 + 2048 * 64
                                            + 4096 * 2048)
    assert parts["gdn_conv"] == 3 * 4 * 8192
    assert parts["attention"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert parts["attention"] == 27_262_976
    assert parts["router"] == 4 * 2048 * 512
    assert parts["shared_expert"] == 4 * (3 * 2048 * 512 + 2048)
    assert parts["held_experts"] == 4 * 1.25 * 3_145_728
    assert parts["head"] == 2048 * 18992
    seq = 8192
    scores = flops_qwen3_next.attention_flops_per_token(sizes, seq)
    assert scores == 6 * 1 * 16 * (256 + 256) * seq
    # the rule, a token and value head: k.k and q.k shared by two heads,
    # the solve by substitution, T applied, three state products, scores
    a_head = 2 * 64 * 128 / 2 + 64 * 64 / 3 + 64 * 256 + 3 * 128 * 128 \
        + 64 * 128
    assert round(a_head) == 83285
    assert flops_qwen3_next.rule_macs_per_token(sizes) == 3 * 32 * a_head
    rule = flops_qwen3_next.rule_flops_per_token(sizes)
    assert rule == 6 * 3 * 32 * a_head
    total = flops_qwen3_next.flops_per_token(sizes, seq)
    assert total == 6 * sum(parts.values()) + scores + rule
    giga = {k: round(6 * v / 1e9, 2) for k, v in parts.items()}
    assert giga == {"gdn_projections": 0.61, "gdn_conv": 0.0,
                    "attention": 0.16, "router": 0.03, "shared_expert": 0.08,
                    "held_experts": 0.09, "head": 0.23}
    assert round(scores / 1e9, 2) == 0.40 and round(rule / 1e9, 3) == 0.048
    assert round(total / 1e9, 2) == 1.65
    assert round(6 * parts["gdn_projections"] / total, 2) == 0.37
    assert round(rule / total, 2) == 0.03
    causal = flops_qwen3_next.attention_flops_per_token(sizes, seq,
                                                        causal=True)
    assert causal == scores / 2 * (seq + 1) / seq
    assert flops_qwen3_next.held_expert_flops_per_token(sizes) == \
        6 * parts["held_experts"]


def test_flops_qwen3_next_equals_the_count_from_parameter_shapes(fam):
    """6 x every 2-D-or-more leaf but the embedding, a held expert leaf
    counted for the 10 / 512 of the router's choices that fall on each."""
    config = _config()
    cfg = fam.model_config(config, config["train"]["model_options"])
    shapes = jax.eval_shape(lambda r: fam.module().init_params(r, cfg),
                            jax.random.key(0))
    touched = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        key = jax.tree_util.keystr(path)
        if "wte" in key or "scale" in key or "A_log" in key \
                or "dt_bias" in key:
            continue
        touched += leaf.size * (10 / 512 if "experts" in key else 1)
    sizes = fam.sizes(config)
    assert sum(flops_qwen3_next.matmul_params_per_token(sizes).values()) == \
        touched


def test_the_manifest_has_the_configuration_the_cell_and_the_metrics():
    bench = manifest.load_manifest()
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    # by name, wherever later PRs' entries put them: each once
    assert sorted(m["name"] for m in mine) == sorted(NEW_METRICS)
    for m in mine:
        assert CELL in m["workloads"]
        assert m["source"] == "device_trace"
        assert m["moves"] == "train_tokens_per_s_per_chip"
        spec = manifest.metric_spec("per_layer", m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
    cell_metrics = {m["name"] for m in
                    manifest.metrics_of_cell(bench, "per_layer", CELL)}
    assert cell_metrics >= set(NEW_METRICS) | set(SHARED_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in SHARED_METRICS:
            assert m["workloads"].count(CELL) == 1
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "train_tokens_per_s_per_chip")
    assert tokens["workloads"].count(CELL) == 1
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "train-b2-s8192", 1)
    assert "eight chips' tokens for one chip's experts" in cell["why"]
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == REDUCED
    assert config["source"] == _config()["source"]
    # since PR 63 no test pins a metric's list of cells: the two scope
    # metrics of the routed training cells list this one too, once, and
    # their copies under names of its own are gone
    for name in ("moe.dispatch_ms", "moe.experts_scope_ms"):
        m = manifest.find(bench["per_layer"], name, "metric")
        assert m["workloads"].count(CELL) == 1
    assert not [m for m in bench["per_layer"]
                if m["name"].startswith("moe.train_share_")
                and m["name"] != "moe.train_share_expert_peak_share"]


def _joined(events):
    return {"window": [10.0, 11.0], "events": {"/device:TPU:0": [
        ("train.step", name, 10.0, seconds, {"scope": scope, "pass": "bwd"})
        for name, seconds, scope in events]}}


def test_the_new_metrics_read_their_scopes_and_kernels_and_no_other(
        fam, monkeypatch):
    specs = {n: manifest.metric_spec("per_layer", n) for n in NEW_METRICS}
    assert specs["attn.gated_flash_peak_share"]["params"]["shapes"] == \
        specs["attn.gated_flash_ms"]["params"]["shapes"]
    # a 2 s window of which the traced 1 s holds, over 2 steps of 16,384
    # tokens: kernels by the names the v5e gave them (my chip run, PR 57)
    traced = {"window": [10.0, 11.0], "host": [], "device": {"/device:TPU:0": [
        ["tpu_custom_call.82 bf16[32,8192,256]", 10.0, 0.02],     # flash bwd
        ["flash_fwd.8 bf16[32,8192,256]", 10.2, 0.01],
        ["%while.134", 10.0, 1.0],
        ["gmm.35 bf16[163840,512]", 10.3, 0.01],
        ["gmm.37 bf16[163840,2048]", 10.31, 0.01],
        ["tgmm.3 bf16[64,512,2048]", 10.32, 0.01],
        ["tgmm.4 bf16[64,2048,512]", 10.33, 0.01],
        ["spread_held_rows.52 bf16[163840,2048]", 10.4, 0.2],
        ["sum_held_slots.39 bf16[16384,2048]", 10.6, 0.1]]}}
    joined = _joined([
        ("fusion.1", 0.10, "grads/gdn/gdn_rule"),
        ("fusion.2", 0.02, "grads/gdn/gdn_rule/_span"),
        ("fusion.3", 0.03, "grads/gdn/gdn_conv"),
        ("fusion.4", 0.05, "grads/gdn/gdn_in"),
        ("fusion.5", 0.01, "grads/gdn/gdn_norm"),
        ("fusion.6", 0.01, "grads/gdn/gdn_out"),
        ("sum_held_slots.39", 0.04, "grads/moe/moe_combine/sum_held_slots"),
        ("spread_held_rows.52", 0.02, "grads/moe/moe_dispatch"),
        ("gmm.35", 0.07, "grads/moe/moe_experts"),
        ("fusion.9", 0.3, "grads/attn_qkv")])
    monkeypatch.setattr(op_scopes, "of_run",
                        lambda facts: joined if facts.get("trace") else None)
    facts = {"trace": traced, "steps": 2, "window_s": 2.0, "chips": 1,
             "tokens": 2 * 16384, "peak_flops_per_s": 197e12}

    def read(name, facts=facts):
        spec = specs[name]
        return manifest.reducer(spec["reducer"])(facts, spec["params"])
    assert read("gdn.rule_ms") == pytest.approx(120.0)
    assert read("gdn.conv_ms") == pytest.approx(30.0)
    assert read("gdn.mixer_ms") == pytest.approx(220.0)
    assert read("moe.dispatch_ms") == pytest.approx(60.0)
    assert read("moe.experts_scope_ms") == pytest.approx(70.0)
    assert read("attn.gated_flash_ms") == pytest.approx(30.0)
    sizes = fam.sizes(_config())
    rule = flops_qwen3_next.rule_flops_per_token(sizes) * 16384
    assert read("gdn.rule_peak_share") == pytest.approx(
        100 * rule / 0.12 / 197e12)
    attention = flops_qwen3_next.attention_flops_per_token(
        sizes, 8192, causal=True) * 16384
    assert read("attn.gated_flash_peak_share") == pytest.approx(
        100 * attention / 0.03 / 197e12)
    experts = flops_qwen3_next.held_expert_flops_per_token(sizes) * 16384
    assert read("moe.train_share_expert_peak_share") == pytest.approx(
        100 * experts / 0.04 / 197e12)
    for name in NEW_METRICS:
        if name.endswith("peak_share"):
            assert 0 < read(name) < 100, name
    # nothing to read: no trace, a CPU rehearsal without a peak, a program
    # without such scopes or kernels (the parent's): None, and no error
    none = {**traced, "device": {"/device:TPU:0": [["fusion.7", 10.2, 0.2]]}}
    joined = _joined([("fusion.9", 0.3, "grads/attn_qkv")])
    for name in NEW_METRICS:
        assert read(name, {**facts, "trace": None}) is None
        assert not read(name, {**facts, "trace": none}), name
    for name in NEW_METRICS:
        if name.endswith("peak_share"):
            assert read(name, {**facts, "peak_flops_per_s": None}) is None
            assert qwen3_next_peak_share.reduce(
                {**facts, "trace": none}, specs[name]["params"]) is None
