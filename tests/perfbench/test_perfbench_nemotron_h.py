"""The Nemotron-H (``nemotron_h``) family file, its operation count, its
metrics and its cell: the configuration keeps every published width
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

from perfbench import flops_nemotron_h, manifest, op_scopes
from perfbench.reducers import nemotron_h_peak_share

CELL = "nemotron-3-nano-30b-a3b.train-b2-s8192"
CONFIG = "nemotron-3-nano-30b-a3b"
CONFIG_FILE = manifest.BENCH_DIR / "configs" / f"{CONFIG}.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size"]
# BENCHMARK.json may hold 128 per-layer metrics and held 125: the three
# of the state-space mixer, which no accepted metric covers, are listed;
# the other four of ISSUE 73 are files and reducer parts that wait for room
LISTED_METRICS = ("ssm.train_mixer_ms", "ssm.train_scan_ms",
                  "ssm.train_scan_peak_share")
NEW_METRICS = LISTED_METRICS + (
    "ssm.train_conv_ms", "ssm.train_norm_ms", "moe.relu2_expert_peak_share",
    "attn.nope_flash_peak_share")
SHARED_METRICS = ("train_program.step_ms", "train_program.mfu",
                  "train_program.optimizer_ms", "train_program.head_loss_ms",
                  "train_program.attn_scope_ms", "train_program.unscoped_ms",
                  "kernels.custom_call_ms", "device.train_idle_share",
                  "moe.dispatch_ms", "moe.experts_scope_ms", "moe.router_ms",
                  "moe.router_choice_ms", "setup.program_s", "setup.trace_s",
                  "setup.lower_s", "setup.backend_s", "setup.cache_misses",
                  "setup.programs")


def _config():
    return json.loads(CONFIG_FILE.read_text())


@pytest.fixture(scope="module")
def fam():
    return manifest.family("nemotron_h")


def test_the_configuration_keeps_every_published_width(fam):
    config = _config()
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.ssm_chunk) == \
        (2688, 64, 64, 8, 128, 4, 128)
    assert (cfg.d_ssm, cfg.conv_dim) == (4096, 6144)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.expert_dim,
            cfg.shared_dim, cfg.routed_scale) == (128, 6, 1856, 3712, 2.5)
    assert cfg.rms_eps == 1e-5 and cfg.max_positions == 262144
    # the share: what is reduced, and what it was
    assert config["reduced"] == REDUCED
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert (cfg.pattern, cfg.n_layer, cfg.n_held_experts,
            cfg.first_held_expert, cfg.vocab_size, cfg.init_depth) == \
        ("MEMEM*EME", 9, 16, 0, 16384, 52)
    assert config["published"]["hybrid_override_pattern"].startswith(
        cfg.pattern)
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert config["deployment"]["held_expert_ids"] == list(range(16))
    assert cfg.vocab_size * 8 == 131072 and cfg.n_held_experts * 8 == 128
    for key in ("deployment", "distorts", "assumed", "why_reduced"):
        assert config[key]
    assert "attention and the scan see eight chips' tokens for one chip's " \
           "experts" in config["distorts"][0]
    assert "no rotary embedding" in config["assumed"][0]
    assert cfg.param_dtype == jnp.bfloat16 and cfg.remat_policy == "attn"
    # what the chip holds is what the file's cut says it is
    shapes = jax.eval_shape(lambda r: fam.module().init_params(r, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 986.3
    assert "986.3 M parameters" in config["deployment"]["held"]
    experts = shapes["expert_blocks"]["experts"]
    assert set(experts) == {"w_up", "w_down"}        # no gate matrix
    assert experts["w_up"].shape == (4, 16, 2688, 1856)
    wide = {jax.tree_util.keystr(p): x.dtype for p, x in
            jax.tree_util.tree_leaves_with_path(shapes)
            if x.dtype != jnp.bfloat16}
    assert sorted(k.split("'")[-2] for k in wide) == [
        "A_log", "D", "dt_bias", "select_bias"]


def test_every_number_of_the_catalog_is_in_the_file():
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    config = _config()
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows if r["source_url"] == config["source"])
    assert row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
    differ = {k for k, v in row["config"].items()
              if config.get(k, "absent") != v}
    assert differ == set(REDUCED)
    assert all(config["published"][k] == row["config"][k] for k in REDUCED)


@pytest.mark.parametrize("change", [
    {"hybrid_override_pattern": "MEMEM-EME"},           # a dense MLP layer
    {"hybrid_override_pattern": "MEMEMXEME"},
    {"hybrid_override_pattern": "MEMEM*EM", "num_hidden_layers": 9},
    {"hybrid_override_pattern": "EEMEM*EME"},            # no run of the whole
    {"num_hidden_layers": 8}, {"mlp_hidden_act": "silu"},
    {"mamba_hidden_act": "gelu"}, {"use_conv_bias": False},
    {"use_bias": True}, {"attention_bias": True},
    {"tie_word_embeddings": True}, {"norm_topk_prob": False},
    {"n_group": 2}, {"topk_group": 2}, {"n_shared_experts": 2},
    {"sliding_window": 4096}, {"model_type": "mamba2"},
    {"n_routed_experts": 8}, {"n_groups": 7},
])
def test_a_block_the_program_does_not_have_is_refused(fam, change):
    config = {**_config(), **change}
    with pytest.raises(ValueError):
        fam.check_sizes(config)
    with pytest.raises(ValueError):
        fam.model_config(config, config["train"]["model_options"])


def test_a_share_that_is_not_the_routers_is_refused(fam):
    for held in (list(range(120, 136)), list(range(0, 32, 2))):
        config = _config()
        config["deployment"] = {**config["deployment"],
                                "held_expert_ids": held}
        with pytest.raises(ValueError):
            fam.check_sizes(config)


def test_another_stage_of_the_pattern_is_fine(fam):
    config = {**_config(), "hybrid_override_pattern": "MEM*EMEME"}
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.count("M"), cfg.count("E"), cfg.count("*")) == (4, 4, 1)


def _rehearsal_cell():
    from perfbench.run import _rehearsal_cell
    return _rehearsal_cell(manifest.load_cell(manifest.load_manifest(), CELL))


def test_the_rehearsals_gpt2_named_overrides_shrink_this_model(fam):
    config = _rehearsal_cell()["config_file"]
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.pattern, cfg.n_head, cfg.n_kv_head) == \
        (64, "MEMEM*EME", 4, 2)
    assert (cfg.vocab_size, cfg.max_positions) == (256, 64)
    assert (cfg.head_dim, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.ssm_chunk, cfg.expert_dim, cfg.shared_dim) \
        == (8, 4, 8, 2, 8, 8, 44, 88)
    # the router, the share and the choice stay
    assert (cfg.n_routed_experts, cfg.n_held_experts,
            cfg.experts_per_token) == (128, 16, 6)
    assert cfg.remat_policy == "full" and cfg.dtype == jnp.float32


def _ctx(cell):
    return {**cell, "seed": 2 ** 31 + 5, "seconds": 0.2, "trace": False,
            "rehearse": True, "notes": False, "devices": jax.devices()[:1],
            "t_start": time.perf_counter(), "marks": {}, "trace_dir": "",
            "peaks": None}


def test_the_training_jobs_own_run_agrees_with_the_reference(fam):
    """``jobs/train.run`` on this model at the rehearsal's size (the cell's
    ``--rehearse`` run without the process round it): ``correct``, the
    program's loss on the check sequences the float32 reference's to 1e-5
    (both float32 here; tests/test_nemotron_h.py shows that a wrong
    convention would be caught), and every metric of the cell that has
    something to read off a CPU printed: the device-trace ones read a
    trace the CPU does not give, and return None without raising."""
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
    assert set(printed) >= {"train_tokens_per_s_per_chip", "setup_s",
                            "train_program.step_ms"}
    assert not set(printed) & set(NEW_METRICS)
    assert all(v > 0 for k, v in printed.items()
               if not k.startswith("setup."))


def test_the_traffic_file_is_the_one_that_was_there():
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    spec = cell["traffic_file"]
    assert (spec["kind"], spec["batch"], spec["seq"], spec["ring"],
            spec["warmup_steps"], spec["sync_lag"], spec["min_steps"],
            spec["trace_seconds"], spec["check_sequences"]) == \
        ("train", 2, 8192, 4, 2, 2, 10, 6, 2)
    assert cell["traffic"] == "train-b2-s8192"


def test_flops_nemotron_h_equals_a_hand_count_and_the_files_figures(fam):
    """ISSUE 73's count from the config's keys, by part in GFLOP a token
    at 8,192 positions, and the shares the file's ``distorts`` states."""
    config = _config()
    sizes = fam.sizes(config)
    parts = flops_nemotron_h.matmul_params_per_token(sizes)
    assert parts["mamba_projections"] == 4 * (2688 * 10304 + 4096 * 2688)
    assert parts["mamba_conv"] == 4 * 4 * 6144
    assert parts["attention"] == 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    assert parts["router"] == 4 * 2688 * 128
    assert parts["shared_expert"] == 4 * 2 * 2688 * 3712
    assert parts["held_experts"] == 4 * 0.75 * 2 * 2688 * 1856
    assert parts["head"] == 2688 * 16384
    seq = 8192
    # the scan, a token and head: C . B shared by 8 heads, the scores on
    # the inputs, the chunk's addition to the state, the entering state
    a_head = 128 * 128 / 8 + 128 * 64 + 2 * 128 * 64
    assert a_head == 26624
    assert flops_nemotron_h.scan_macs_per_token(sizes) == 4 * 64 * a_head
    scan = flops_nemotron_h.scan_flops_per_token(sizes)
    assert scan == 6 * 4 * 64 * a_head
    scores = flops_nemotron_h.attention_flops_per_token(sizes, seq)
    assert scores == 6 * 1 * 32 * (128 + 128) * (seq + 1) / 2
    whole = flops_nemotron_h.attention_flops_per_token(sizes, seq,
                                                       causal=False)
    assert whole == 6 * 32 * 256 * seq
    total = flops_nemotron_h.flops_per_token(sizes, seq)
    assert total == 6 * sum(parts.values()) + scores + scan
    by_part = flops_nemotron_h.parts_flops_per_token(sizes, seq)
    assert sum(by_part.values()) == total
    giga = {k: round(v / 1e9, 2) for k, v in by_part.items()}
    assert giga == {"mamba_projections": 0.93, "mamba_conv": 0.0,
                    "mamba_scan": 0.04, "attention": 0.14,
                    "attention_scores": 0.20, "router": 0.01,
                    "shared_expert": 0.48, "held_experts": 0.18,
                    "head": 0.26}
    assert round(total / 1e9, 2) == 2.24
    mamba = sum(by_part[k] for k in by_part if k.startswith("mamba"))
    experts = sum(by_part[k] for k in ("router", "shared_expert",
                                       "held_experts"))
    attention = by_part["attention"] + by_part["attention_scores"]
    shares = [round(100 * v / total) for v in
              (mamba, experts, attention, by_part["head"])]
    assert shares == [43, 30, 15, 12]
    figures = config["distorts"][-1]
    for text in ("2.24 GFLOP a token", "0.97 (43%", "0.67 (30%",
                 "0.34 (15%", "head 0.26 (12%)", "the step 2.44"):
        assert text in figures, text
    assert round((total - scores + whole) / 1e9, 2) == 2.44
    assert flops_nemotron_h.held_expert_flops_per_token(sizes) == \
        6 * parts["held_experts"]
    cell = manifest.find(manifest.load_manifest()["workloads"], CELL, "cell")
    assert "4 Mamba-2 layers 43% of counted work" in cell["why"]
    assert "4 relu^2 expert layers 30%" in cell["why"]


def test_flops_nemotron_h_equals_the_count_from_parameter_shapes(fam):
    """6 x every 2-D-or-more leaf but the embedding, a held expert leaf
    counted for the 6 / 128 of the router's choices that fall on each."""
    config = _config()
    cfg = fam.model_config(config, config["train"]["model_options"])
    shapes = jax.eval_shape(lambda r: fam.module().init_params(r, cfg),
                            jax.random.key(0))
    touched = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        key = jax.tree_util.keystr(path)
        if "wte" in key or leaf.ndim < 3 and "lm_head" not in key:
            continue                    # norms, biases, a head's scalars
        touched += leaf.size * (6 / 128 if "experts'" in key else 1)
    sizes = fam.sizes(config)
    assert sum(flops_nemotron_h.matmul_params_per_token(sizes).values()) == \
        touched


def test_the_manifest_has_the_configuration_the_cell_and_the_metrics():
    bench = manifest.load_manifest()
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    # by name, wherever later PRs' entries put them: each once
    assert sorted(m["name"] for m in mine) == sorted(LISTED_METRICS)
    assert len(bench["per_layer"]) <= 128
    for m in mine:
        assert m["workloads"] == [CELL] or CELL in m["workloads"]
        assert m["source"] == "device_trace"
        assert m["moves"] == "train_tokens_per_s_per_chip"
        spec = manifest.metric_spec("per_layer", m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["layer"] == {"ssm": "ssm", "moe": "moe",
                              "attn": "kernels"}[m["name"].split(".")[0]]
        if m["name"].endswith("peak_share"):
            assert (m["unit"], m["better"]) == ("%", "higher")
            assert spec["reducer"] == "nemotron_h_peak_share"
            assert spec["params"]["config"] == \
                f"perfbench/configs/{CONFIG}.json"
    cell_metrics = {m["name"] for m in
                    manifest.metrics_of_cell(bench, "per_layer", CELL)}
    assert cell_metrics >= set(LISTED_METRICS) | set(SHARED_METRICS)
    for name in set(NEW_METRICS) - set(LISTED_METRICS):
        spec = manifest.metric_spec("per_layer", name)      # the file waits
        assert spec["moves"] == "train_tokens_per_s_per_chip"
    for m in bench["per_layer"]:
        if m["name"] in SHARED_METRICS:
            assert m["workloads"].count(CELL) == 1
    tokens = manifest.find(bench["end_to_end"],
                           "train_tokens_per_s_per_chip", "metric")
    assert tokens["workloads"].count(CELL) == 1
    assert "workloads" not in manifest.find(bench["end_to_end"], "setup_s",
                                            "metric")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "train-b2-s8192", 1)
    assert "8 chips' tokens for 1 chip's experts" in cell["why"]
    assert "bypasses collectives" in cell["why"] and len(cell["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == REDUCED
    assert config["source"] == _config()["source"]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"


def _joined(events):
    return {"window": [10.0, 11.0], "events": {"/device:TPU:0": [
        ("train.step", name, 10.0, seconds, {"scope": scope, "pass": "bwd"})
        for name, seconds, scope in events]}}


def test_the_new_metrics_read_their_scopes_and_kernels_and_no_other(
        fam, monkeypatch):
    specs = {n: manifest.metric_spec("per_layer", n) for n in NEW_METRICS}
    # a 2 s window of which the traced 1 s holds, over 2 steps of 16,384
    # tokens: kernels by the names the v5e gives them
    traced = {"window": [10.0, 11.0], "host": [], "device": {"/device:TPU:0": [
        ["flash_bwd.3 bf16[64,8192,128]", 10.0, 0.02],
        ["flash_fwd.2 bf16[64,8192,128]", 10.2, 0.01],
        ["%while.134", 10.0, 1.0],
        ["gmm.35 bf16[98304,1856]", 10.3, 0.02],
        ["gmm.37 bf16[98304,2688]", 10.31, 0.02],
        ["tgmm.3 bf16[16,2688,1856]", 10.32, 0.02],
        ["tgmm.4 bf16[16,1856,2688]", 10.33, 0.02],
        ["gmm.40 bf16[98304,768]", 10.34, 0.5],          # another model's
        ["router_choice.1 s32[8,16384]", 10.4, 0.2]]}}
    joined = _joined([
        ("fusion.1", 0.20, "grads/ssm/ssm_scan"),
        ("fusion.2", 0.04, "grads/ssm/ssm_scan/ssd_scan"),
        ("fusion.3", 0.03, "grads/ssm/ssm_conv"),
        ("fusion.4", 0.05, "grads/ssm/ssm_in"),
        ("fusion.5", 0.02, "grads/ssm/ssm_norm"),
        ("fusion.6", 0.01, "grads/ssm/ssm_out"),
        ("gmm.35", 0.08, "grads/moe/moe_experts"),
        ("fusion.8", 0.01, "grads/moe/shared"),
        ("fusion.9", 0.3, "grads/attn_qkv")])
    monkeypatch.setattr(op_scopes, "of_run",
                        lambda facts: joined if facts.get("trace") else None)
    facts = {"trace": traced, "steps": 2, "window_s": 2.0, "chips": 1,
             "tokens": 2 * 16384, "peak_flops_per_s": 197e12}

    def read(name, facts=facts):
        spec = specs[name]
        return manifest.reducer(spec["reducer"])(facts, spec["params"])
    assert read("ssm.train_scan_ms") == pytest.approx(240.0)
    assert read("ssm.train_conv_ms") == pytest.approx(30.0)
    assert read("ssm.train_norm_ms") == pytest.approx(20.0)
    assert read("ssm.train_mixer_ms") == pytest.approx(350.0)
    sizes = fam.sizes(_config())
    scan = flops_nemotron_h.scan_flops_per_token(sizes) * 16384
    assert read("ssm.train_scan_peak_share") == pytest.approx(
        100 * scan / 0.24 / 197e12)
    attention = flops_nemotron_h.attention_flops_per_token(sizes, 8192) \
        * 16384
    assert read("attn.nope_flash_peak_share") == pytest.approx(
        100 * attention / 0.03 / 197e12)
    experts = flops_nemotron_h.held_expert_flops_per_token(sizes) * 16384
    assert read("moe.relu2_expert_peak_share") == pytest.approx(
        100 * experts / 0.08 / 197e12)
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
            assert nemotron_h_peak_share.reduce(
                {**facts, "trace": none}, specs[name]["params"]) is None
