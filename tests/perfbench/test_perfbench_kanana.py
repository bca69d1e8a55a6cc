"""The Kanana-2 (``deepseek_v3``) family file, its operation count, its
four metrics and its cell: the configuration keeps every published width
and states its share; a file whose block the program does not have is
refused; the training job's own ``run`` agrees with the reference at the
rehearsal's size, and a rehearsal prints the metrics that need no chip."""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops_kanana, manifest
from perfbench.reducers import kanana_peak_share

CELL = "kanana-2-30b-a3b.train-b2-s8192"
CONFIG_FILE = manifest.BENCH_DIR / "configs" / "kanana-2-30b-a3b.json"
NEW_METRICS = ("mla.attention_ms", "mla.attention_peak_share",
               "moe.held_expert_ms", "moe.held_expert_peak_share")
SHARED_METRICS = ("train_program.step_ms", "train_program.mfu",
                  "kernels.custom_call_ms", "device.train_idle_share")


def _config():
    return json.loads(CONFIG_FILE.read_text())


@pytest.fixture(scope="module")
def fam():
    return manifest.family("deepseek_v3")


def test_the_configuration_keeps_every_published_width(fam):
    config = _config()
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.n_head, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim, cfg.kv_latent_dim) == (2048, 32, 128, 64, 128, 512)
    assert (cfg.ffn_dim, cfg.expert_dim, cfg.n_shared_experts,
            cfg.experts_per_token, cfg.n_routed_experts) == \
        (6144, 768, 2, 6, 128)
    assert cfg.routed_scale == 2.448 and cfg.rope_theta == 1e6
    assert cfg.rms_eps == 1e-6 and cfg.max_positions == 32768
    # the share: what is reduced, and what it was
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert (cfg.n_layer, cfg.n_dense_layer, cfg.n_held_experts,
            cfg.first_held_expert, cfg.vocab_size) == (8, 1, 16, 0, 16032)
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert cfg.vocab_size * 8 == 128256 and cfg.n_held_experts * 8 == 128
    for key in ("deployment", "distorts", "assumed", "why_reduced"):
        assert config[key]
    assert cfg.param_dtype == jnp.bfloat16 and cfg.remat_policy == "attn"
    # what the chip holds is what the file's cut says it is
    shapes = jax.eval_shape(lambda r: fam.module().init_params(r, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 910.6


@pytest.mark.parametrize("change", [
    {"q_lora_rank": 1536}, {"scoring_func": "softmax"}, {"n_group": 8},
    {"topk_method": "greedy"}, {"norm_topk_prob": False},
    {"rope_interleave": False}, {"rope_scaling": {"type": "yarn"}},
    {"model_type": "deepseek_v2"}, {"n_routed_experts": 32},
    {"qk_head_dim": 128},
])
def test_a_block_the_program_does_not_have_is_refused(fam, change):
    config = {**_config(), **change}
    with pytest.raises(ValueError):
        fam.check_sizes(config)
    with pytest.raises(ValueError):
        fam.model_config(config, config["train"]["model_options"])


def test_a_share_that_is_not_the_routers_is_refused(fam):
    config = _config()
    config["deployment"] = {**config["deployment"],
                            "held_expert_ids": list(range(120, 136))}
    with pytest.raises(ValueError):
        fam.check_sizes(config)


def _rehearsal_cell():
    from perfbench.run import _rehearsal_cell
    return _rehearsal_cell(manifest.load_cell(manifest.load_manifest(), CELL))


def test_the_rehearsals_gpt2_named_overrides_shrink_this_model(fam):
    cell = _rehearsal_cell()
    config = cell["config_file"]
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.n_layer, cfg.n_dense_layer, cfg.n_head) == \
        (64, 2, 1, 4)
    assert (cfg.vocab_size, cfg.max_positions) == (256, 64)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            cfg.kv_latent_dim, cfg.ffn_dim, cfg.expert_dim) == \
        (8, 8, 8, 16, 192, 24)
    # the router, the share and the choice stay
    assert (cfg.n_routed_experts, cfg.n_held_experts,
            cfg.experts_per_token, cfg.n_shared_experts) == (128, 16, 6, 2)
    assert cfg.remat_policy == "full" and cfg.dtype == jnp.float32


def _ctx(cell):
    return {**cell, "seed": 2 ** 31 + 5, "seconds": 0.2, "trace": False,
            "rehearse": True, "notes": False, "devices": jax.devices()[:1],
            "t_start": time.perf_counter(), "marks": {}, "trace_dir": "",
            "peaks": None}


def test_the_training_jobs_own_run_agrees_with_the_reference(fam):
    """``jobs/train.run`` on this model at the rehearsal's size: the
    program's loss on the check sequences equals the float32 reference's
    to 1e-5 (both float32 here; tests/test_deepseek_v3.py shows at
    decisive weights that a wrong convention would be caught)."""
    from perfbench.jobs import train
    cell = _rehearsal_cell()
    facts = train.run(_ctx(cell))
    assert facts["correct"] and all(facts["checks"].values())
    assert facts["notes"]["loss_abs_diff"] < 1e-5
    assert facts["notes"]["loss_atol"] == train.LOSS_ATOL
    assert facts["steps"] >= cell["traffic_file"]["min_steps"]
    assert facts["flops_per_token"] == fam.flops_per_token(
        cell["config_file"], cell["traffic_file"]["seq"])
    # what a rehearsal prints of the cell's metrics (run.py's loop; the
    # subprocess is tests/perfbench/test_perfbench_run.py's, for every
    # cell): both end-to-end metrics, the step time, and nothing that
    # needs a chip's peak or a device lane
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


def test_flops_kanana_equals_a_hand_count(fam):
    """The issue's count from the config's keys: attention 26,345,472
    parameters a layer, one expert 4,718,592, and by part in GFLOP a
    token at 8,192 positions."""
    sizes = fam.sizes(_config())
    parts = flops_kanana.matmul_params_per_token(sizes)
    assert parts["attention"] == 8 * 26_345_472
    assert parts["attention"] // 8 == 2048 * 32 * 192 + 2048 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 2048
    assert parts["dense_mlp"] == 3 * 2048 * 6144
    assert parts["router"] == 7 * 262_144
    assert parts["shared_experts"] == 7 * 9_437_184
    assert parts["held_experts"] == 7 * 0.75 * 4_718_592
    assert parts["head"] == 2048 * 16032
    seq = 8192
    scores = flops_kanana.attention_flops_per_token(sizes, seq)
    assert scores == 6 * 8 * 32 * (192 + 128) * seq
    total = flops_kanana.flops_per_token(sizes, seq)
    assert total == 6 * sum(parts.values()) + scores
    giga = {k: round(6 * v / 1e9, 2) for k, v in parts.items()}
    assert giga == {"attention": 1.26, "dense_mlp": 0.23, "router": 0.01,
                    "shared_experts": 0.40, "held_experts": 0.15,
                    "head": 0.20}
    assert round(scores / 1e9, 2) == 4.03 and round(total / 1e9, 2) == 6.27
    assert round(scores / total, 2) == 0.64
    # what a causal kernel must compute is half of it and a key
    causal = flops_kanana.attention_flops_per_token(sizes, seq, causal=True)
    assert causal == scores / 2 * (seq + 1) / seq
    assert flops_kanana.held_expert_flops_per_token(sizes) == \
        6 * parts["held_experts"]


def test_flops_kanana_equals_the_count_from_parameter_shapes(fam):
    """6 x every 2-D-or-more leaf but the embedding, a held expert leaf
    counted for the 6 / 128 of the router's choices that fall on each."""
    config = _config()
    cfg = fam.model_config(config, config["train"]["model_options"])
    shapes = jax.eval_shape(lambda r: fam.module().init_params(r, cfg),
                            jax.random.key(0))
    touched = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        key = jax.tree_util.keystr(path)
        if "wte" in key or "scale" in key or "select_bias" in key:
            continue
        touched += leaf.size * (6 / 128 if "experts" in key else 1)
    sizes = fam.sizes(config)
    assert sum(flops_kanana.matmul_params_per_token(sizes).values()) == \
        touched


def test_the_new_metrics_are_appended_for_this_cell_only():
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
    # and whatever later PRs gave the cell to report besides (PR 36's scopes)
    assert cell_metrics >= set(NEW_METRICS) | set(SHARED_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in SHARED_METRICS:
            assert m["workloads"].count(CELL) == 1
    assert [w["config"] for w in bench["workloads"] if w["name"] == CELL] \
        == ["kanana-2-30b-a3b"]
    assert [c["name"] for c in bench["configs"]].count("kanana-2-30b-a3b") == 1
    # OLMoE's expert metrics are keyed on OLMoE's result shapes: this cell
    # is not among their cells, whoever else is
    for m in bench["per_layer"]:
        if m["name"].startswith("moe.expert_matmul"):
            assert "olmoe-1b-7b.train-b2-s4096" in m["workloads"] \
                and CELL not in m["workloads"]


def test_the_new_metrics_read_their_kernels_and_no_other(fam):
    specs = {n: manifest.metric_spec("per_layer", n) for n in NEW_METRICS}
    for ms, share in (NEW_METRICS[:2], NEW_METRICS[2:]):
        assert {k: specs[share]["params"][k] for k in ("names", "shapes")} \
            == specs[ms]["params"]
    # names as the v5e gave them (my chip run, PR 34).  A 2 s window of
    # which the traced 1 s holds 0.3 s of attention kernels and 0.04 s of
    # the held experts', over 2 steps of 16,384 tokens
    traced = {"window": [10.0, 11.0], "host": [], "device": {"/device:TPU:0": [
        ["tpu_custom_call.118 bf16[64,8192,192]", 10.0, 0.2],     # flash bwd
        ["tpu_custom_call.122 bf16[64,8192,128]", 10.2, 0.1],     # flash fwd
        ["%while.134", 10.0, 1.0],
        ["gmm.20 bf16[98304,768]", 10.3, 0.01],
        ["tpu_custom_call.97 bf16[98304,2048]", 10.31, 0.01],
        ["tpu_custom_call.99 bf16[16,2048,768]", 10.32, 0.01],
        ["tpu_custom_call.98 bf16[16,768,2048]", 10.33, 0.01],
        ["fusion.1317 bf16[98304,2048]", 10.4, 0.2],              # a gather
        ["copy.926 bf16[2,32,8192,192]", 10.6, 0.1]]}}
    facts = {"trace": traced, "steps": 2, "window_s": 2.0, "chips": 1,
             "tokens": 2 * 16384, "peak_flops_per_s": 197e12}

    def read(name, facts=facts):
        spec = specs[name]
        return manifest.reducer(spec["reducer"])(facts, spec["params"])
    assert read("mla.attention_ms") == pytest.approx(300.0)
    assert read("moe.held_expert_ms") == pytest.approx(40.0)
    sizes = fam.sizes(_config())
    attention = flops_kanana.attention_flops_per_token(sizes, 8192,
                                                       causal=True) * 16384
    assert read("mla.attention_peak_share") == pytest.approx(
        100 * attention / 0.3 / 197e12)
    experts = flops_kanana.held_expert_flops_per_token(sizes) * 16384
    assert read("moe.held_expert_peak_share") == pytest.approx(
        100 * experts / 0.04 / 197e12)
    assert 0 < read("mla.attention_peak_share") < 100
    assert 0 < read("moe.held_expert_peak_share") < 100
    # nothing to read: no trace, a CPU rehearsal without a peak, a program
    # without such kernels (the parent's): None, and no error
    none = {**traced, "device": {"/device:TPU:0": [["fusion.7", 10.2, 0.2]]}}
    for name in NEW_METRICS:
        assert read(name, {**facts, "trace": None}) is None
        assert read(name, {**facts, "trace": none}) is None
    for name in (NEW_METRICS[1], NEW_METRICS[3]):
        assert read(name, {**facts, "peak_flops_per_s": None}) is None
    assert kanana_peak_share.reduce(
        {**facts, "trace": none}, specs[NEW_METRICS[1]]["params"]) is None
