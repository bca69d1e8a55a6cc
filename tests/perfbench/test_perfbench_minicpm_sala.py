"""The ``minicpm_sala`` family in the harness: its configuration file against
the program's preset and the catalog, the manifest's entries by name, the
traffic's grid and rate, the serving job itself on ``minicpm_sala:tiny`` (a
``--rehearse`` of the cell runs the toy GPT-2, which neither selects pages
nor keeps a state nor prefills in chunks, so the family's own model goes
through the job here, at the sizes of ``rehearsal/minicpm_sala.json``), the
bytes and operations against hand counts, and the new reducers on made-up
events.

The tiny model is float32, so its check reads what float32 arithmetic in
another order leaves: under 1e-3 on the CPU (seeds 0-7), held to 5e-3 here;
the reference handed float8_e4m3 weights reads 0.1 or more.
"""

import json
import time
from pathlib import Path

import pytest

from perfbench import bytes_minicpm_sala as sala_bytes
from perfbench import manifest
from perfbench.families import minicpm_sala as family

CELL = "minicpm-sala-9b.serve-longdoc-sparse"
CONFIG = "minicpm-sala-9b"
TRAFFIC = "serve-longdoc-sparse"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MINE = ("sparse.decode_select_ms", "sparse.decode_attn_ms",
        "lightning.decode_mixer_ms", "sparse.decode_attn_hbm_share",
        "lightning.decode_state_hbm_share", "sparse.decode_pages_read_share",
        "engine.prefill_chunk_ms", "sparse.prefill_select_ms",
        "sparse.prefill_attn_ms", "lightning.prefill_scan_ms",
        "sparse.prefill_attn_peak_share")
SHARED = ("engine.ttft_p50_ms", "scheduler.batch_occupancy",
          "scheduler.preemptions", "scheduler.queue_wait_mean_ms",
          # PR 54's token stamps and idle by cause, listed since PR 63
          "engine.token_gap_p50_ms", "engine.token_gap_p95_ms",
          "device.idle_unoffered_share", "device.idle_with_work_share",
          "device.idle_per_prefill_ms", "engine.compiles_in_window")
TINY_ATOL = 5e-3


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


# ------------------------------------------------ the files and the manifest
def test_the_configuration_is_the_programs_preset(cell):
    from ray_tpu.models import minicpm_sala
    config = cell["config_file"]
    preset = minicpm_sala.PRESETS["minicpm-sala-9b-l16"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] \
        == "minicpm_sala:minicpm-sala-9b-l16"
    assert config["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert config["published"]["num_hidden_layers"] == 32
    assert tuple(config["published"]["mixer_types"]) \
        == minicpm_sala.PUBLISHED_MIXERS
    for key in ("deployment", "distorts", "assumed"):
        assert config[key]
    # every published width, both head counts, the whole vocabulary
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["lightning_nh"],
            config["lightning_head_dim"], config["vocab_size"]) \
        == (4096, 16384, 32, 2, 128, 32, 128, 73448)
    # layers 9-24: sparse at 9, 16, 17 and 22, the published 1 : 3
    held = family.held_types(config)
    assert config["held_layers"] == [9, 25] and len(held) == 16
    assert [i + 9 for i, m in enumerate(held) if m == "minicpm4"] \
        == [9, 16, 17, 22]
    assert minicpm_sala.cache_layers(preset) == {"kv": 4, "state": 12}
    assert config["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64,
        "dense_len": 8192}
    assert any("sparse_config" in item for item in config["assumed"])
    with pytest.raises(ValueError, match="intermediate_size"):
        family.check_sizes({**config, "intermediate_size": 8192}, preset)
    with pytest.raises(ValueError, match="attn_use_rope"):
        family.check_sizes({**config, "attn_use_rope": True}, preset)
    with pytest.raises(ValueError, match="published list"):
        family.check_sizes({**config, "held_layers": [0, 16]}, preset)
    with pytest.raises(ValueError, match="topk"):
        family.check_sizes({**config, "sparse_config": {
            **config["sparse_config"], "topk": 32}}, preset)
    serve = config["serve"]
    assert 0 < serve["logit_atol"] < 1 and "chip" in serve["why_logit_atol"]
    engine = serve["engine"]
    assert (engine["max_num_seqs"], engine["decode_batch_buckets"],
            engine["block_size"], engine["num_blocks"],
            engine["max_model_len"]) == (4, [4], 64, 4224, 67584)
    # the engine's own refusals, here and not on the chip (a rehearsal
    # swaps the engine for the toy one)
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
    differ = {k for k, v in row["config"].items()
              if cell["config_file"].get(k, "absent") != v}
    assert differ == set(cell["config_file"]["reduced"])
    assert cell["config_file"]["published"]["mixer_types"] \
        == row["config"]["mixer_types"]


def test_the_manifest_has_the_configuration_the_cell_and_the_metrics():
    bench = manifest.load_manifest()
    entry = manifest.find(bench["configs"], CONFIG, "config")
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert entry["source"] == json.loads(
        (manifest.ROOT / entry["file"]).read_text())["source"]
    mine = manifest.find(bench["workloads"], CELL, "workload")
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(mine["why"]) <= 200 and "PLACEHOLDER" not in mine["why"]
    assert len([w for w in bench["workloads"]
                if w["config"] == CONFIG]) == 1
    # end to end: the tokens a second and the set-up; no token gap (PERF.md
    # section 4 says why)
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
        assert callable(manifest.reducer(spec["reducer"]))
    assert all(m["moves"] in reported for m in layer.values())
    # a share of a roofline is a percentage and says so in its name
    for name in MINE:
        if name.endswith("_share"):
            assert layer[name]["unit"] == "%"


def test_the_traffic_is_the_issues_grid_below_the_knee(cell):
    from perfbench import traffic
    spec = cell["traffic_file"]
    assert spec["kind"] == "serve"
    assert spec["prompt_tokens"] == {"median": 32768, "sigma": 0.6,
                                     "lo": 12288, "hi": 98304}
    assert spec["output_tokens"] == {"median": 512, "sigma": 0.5,
                                     "lo": 192, "hi": 1024}
    grid = traffic.length_grid(spec)
    assert len(grid) == spec["prompt_quantiles"] * spec["output_quantiles"]
    assert (spec["prompt_quantiles"], spec["output_quantiles"]) in (
        (3, 2), (4, 2), (3, 3), (4, 3))
    # the cycle is the window: a window offers the grid exactly once
    assert spec["cycle_seconds"] == manifest.load_manifest()["run_seconds"]
    sparse = cell["config_file"]["sparse_config"]
    # every prompt is past dense_len (every request selects) and fits
    assert min(p for p, _ in grid) > sparse["dense_len"]
    assert max(p + o for p, o in grid) <= spec["max_context"]
    knee = spec["knee"]
    share = traffic.rate_rps(spec) / knee["knee_rps"]
    assert 0.65 <= share <= 0.85, share
    assert knee["found"] and knee["offered"] \
        and "PLACEHOLDER" not in json.dumps(spec)
    engine = cell["config_file"]["serve"]["engine"]
    chunk = 2048
    assert all(b % chunk == 0 for b in engine["prefill_len_buckets"])
    assert engine["prefill_len_buckets"][-1] >= engine["max_model_len"] \
        == spec["max_context"]
    # each prompt of the grid in the smallest whole number of chunks
    assert sorted({-(-p // chunk) * chunk for p, _ in grid}) \
        == engine["prefill_len_buckets"][:-1]
    assert spec["check_prompt_tokens"] == 12288 > sparse["dense_len"]
    assert spec["check_decode_steps"] == 8
    # four sequences of the longest context have their pages
    assert engine["num_blocks"] * engine["block_size"] \
        == engine["max_num_seqs"] * engine["max_model_len"]


def test_a_shrunk_configuration_is_handed_to_the_gpt2_family(cell):
    """What --rehearse makes of the cell: GPT-2's names present."""
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    shrunk = {**cell["config_file"], **over["config"]}
    assert family.shrunk(shrunk) and not family.shrunk(cell["config_file"])
    from ray_tpu.models import gpt2
    family.check_sizes(shrunk, gpt2.PRESETS["tiny"]())


# --------------------------------------------------- the job on the family
def _tiny_ctx(seed: int) -> dict:
    """The job's context as run.prepare builds it, for minicpm_sala:tiny."""
    from ray_tpu.models import minicpm_sala
    tiny = minicpm_sala.PRESETS["tiny"]()
    toy = json.loads((manifest.BENCH_DIR / "rehearsal" / "minicpm_sala.json")
                     .read_text())
    family.check_sizes({**family.FIXED, **toy["config"],
                        **{k: getattr(tiny, attr)
                           for k, attr in family.KEYS.items()
                           if k not in toy["config"]}}, tiny)
    sizes = family.sizes_of_model(tiny)
    config = {"family": "minicpm_sala", **family.FIXED,
              **{k: sizes[k] for k in family.KEYS},
              "lightning_nkv": tiny.lightning_heads,
              "mixer_types": list(tiny.mixer_types),
              "published": {"num_hidden_layers": tiny.n_layer,
                            "mixer_types": list(tiny.mixer_types)},
              "held_layers": [0, tiny.n_layer],
              "sparse_config": sizes["sparse_config"],
              "serve": {"engine": toy["serve_engine"],
                        "logit_atol": TINY_ATOL,
                        "why_logit_atol": "float32 in another order"}}
    spec = json.loads((manifest.BENCH_DIR / "traffic" / f"{TRAFFIC}.json")
                      .read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **toy["traffic"]},
            "seed": seed, "seconds": 1.0, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def test_the_serving_job_runs_the_family_and_its_check_passes():
    """Served(ctx) -> the window -> check_logits, through the chunked
    prefill (prompts of up to 100 tokens in chunks of 32), the paged sparse
    decode (contexts past the tiny dense_len of 48) and the state rows."""
    from perfbench.jobs import serve
    facts = serve.run(_tiny_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]


def test_the_check_fails_on_float8_weights_in_the_reference():
    """The rule's control: the reference with every matrix in float8_e4m3
    fails by the logits, many times over the limit."""
    import jax
    import jax.numpy as jnp

    from perfbench.jobs import serve
    from ray_tpu.models import minicpm_sala

    def fp8(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w if any(
                getattr(k, "key", None) in minicpm_sala.WIDE_PARAMS
                for k in path)
            else w.astype(jnp.float8_e4m3fn).astype(w.dtype), params)

    served = serve.Served(_tiny_ctx(seed=3))
    try:
        sound = served.check_logits(3)
        low = fp8(served.params)
        plain = served.fam.reference_logits
        served.fam.reference_logits = \
            lambda params, tokens, config: plain(low, tokens, config)
        try:
            control = served.check_logits(3)
        finally:
            served.fam.reference_logits = plain
    finally:
        served.close()
    assert sound["ok"], sound
    assert not control["ok"], control
    assert min(control["prefill_logit_diff"], control["decode_logit_diff"]) \
        > 3 * TINY_ATOL


# ------------------------------------------------------ bytes and operations
def test_the_bytes_and_operations_against_hand_counts(cell):
    config = cell["config_file"]
    E, F = 4096, 16384
    lightning = 5 * E * E + 3 * E * F
    sparse = 3 * E * E + 2 * E * 256 + 3 * E * F
    assert sala_bytes.layer_params(config, "lightning-attn") == lightning \
        == 285_212_672
    assert sala_bytes.layer_params(config, "minicpm4") == sparse \
        == 253_755_392
    assert sala_bytes.stack_params(config) == 12 * lightning + 4 * sparse
    assert sala_bytes.total_params(config) \
        == 12 * lightning + 4 * sparse + 2 * 73448 * E == 5_039_259_648
    # a decode step reads the layers and the head, bf16: 9.5e9 bytes
    assert sala_bytes.decode_weight_bytes(config) \
        == (12 * lightning + 4 * sparse + 73448 * E) * 2
    assert sala_bytes.decode_weight_bytes(config) / 819e9 \
        == pytest.approx(11.57e-3, rel=2e-3)
    # a page of one KV head: K and V, 64 positions x 128 lanes, float32
    assert sala_bytes.page_bytes(config) == 2 * 64 * 128 * 4 == 65_536
    # 64 pages x 2 heads x 4 layers a row
    assert sala_bytes.chosen_page_bytes(config) == 64 * 2 * 4 * 65_536 \
        == 33_554_432
    # a half-kernel every 16 positions, 256 lanes, 4 layers: 65,536
    # positions hold 16.8e6 bytes of them
    assert sala_bytes.kernel_bytes(config, 65536) \
        == 4 * (65536 // 16) * 256 * 4 == 16_777_216
    # a row's state: 12 layers x 32 heads x 128 x 128 float32
    assert sala_bytes.state_bytes_per_row(config) \
        == 12 * 32 * 128 * 128 * 4 == 25_165_824
    assert sala_bytes.decode_state_bytes(config, 4) == 2 * 4 * 25_165_824
    # a chunk: 2 operations a weight of the stack and position
    assert sala_bytes.chunk_matmul_flops(config, 2048) \
        == 2 * 2048 * (12 * lightning + 4 * sparse) \
        == pytest.approx(18.18e12, rel=1e-3)
    # the recurrence: 2 x 2 x 128 x 128 a head, position and layer
    assert sala_bytes.chunk_scan_flops(config, 2048) \
        == 12 * 2048 * 32 * 4 * 128 * 128
    # the sparse attention a chunk at 65,536 positions must do: every head
    # against 4,096 kernels and, twice, against 4,096 chosen positions
    assert sala_bytes.chunk_sparse_attention_flops(config, 2048, 65536) \
        == 4 * 2048 * 32 * 128 * 2 * (4096 + 2 * 4096)
    # what a query may attend to: everything up to dense_len, then 63
    # whole blocks and its own up to itself
    assert [sala_bytes.attended_positions(config, t)
            for t in (0, 8191, 8192, 8255, 8256, 65343)] \
        == [1, 8192, 63 * 64 + 1, 63 * 64 + 64, 63 * 64 + 1, 63 * 64 + 64]
    # the first chunk of a prompt is dense and causal: 2,048 x 2,049 / 2
    assert sala_bytes.chunk_required_attention_flops(
        config, 0, 65344, 2048) == 4 * 32 * 128 * 4 * (2048 * 2049 // 2)
    # a chunk past dense_len: 32 runs of a block's 64 queries
    assert sala_bytes.chunk_required_attention_flops(
        config, 30 * 2048, 65344, 2048) \
        == 4 * 32 * 128 * 4 * 32 * (64 * 63 * 64 + 64 * 65 // 2)
    # the last chunk of a prompt holds 65,344 - 31 x 2,048 = 1,856 queries
    assert sala_bytes.chunk_required_attention_flops(
        config, 31 * 2048, 65344, 2048) \
        == 4 * 32 * 128 * 4 * 29 * (64 * 63 * 64 + 64 * 65 // 2)


# ------------------------------------------------------------- the reducers
def _entry(program, scope):
    return {"scope": f"jit({program})/{scope}", "pass": "", "shape": "",
            "prim": "", "src": "", "path": ""}


def _joined(steps=3, chunks=2):
    """A window of ``steps`` decode steps and ``chunks`` chunks: a step has,
    in each of 4 sparse layers, a selection of 0.1 ms and the paged kernel
    of 0.05 ms (a custom call) behind a layout op of 0.01 ms under the same
    scope; the Lightning layers' state step, 0.2 ms in all, inside a mixer
    of 0.5 ms; a chunk has the scan (5 ms), the selection (a loop's body:
    30 ms) and the flash kernel (25 ms)."""
    events, t = [], 1.0

    def add(program, name, dur, scope):
        nonlocal t
        events.append([program, name, t, dur,
                       _entry("step", scope) if scope else None])
        t += dur

    for _ in range(steps):
        for _ in range(4):
            add("llm.decode.4", "fusion.7", 1e-4, "sparse_select")
            add("llm.decode.4", "fusion.8", 1e-5, "sparse_attn/paged_attention")
            add("llm.decode.4", "tpu_custom_call.2", 5e-5,
                "sparse_attn/paged_attention/paged_decode_listed")
        add("llm.decode.4", "fusion.9", 3e-4, "lightning_mixer/attn_qkv")
        add("llm.decode.4", "fusion.10", 2e-4,
            "lightning_mixer/lightning_step")
        add("llm.decode.4", "fusion.11", 1e-3, "mlp")
    for _ in range(chunks):
        add("llm.prefill.chunk.2048", "fusion.20", 5e-3,
            "lightning_mixer/lightning_scan")
        add("llm.prefill.chunk.2048", "fusion.21", 3e-2,
            "sparse_select/while/body/closed_call")
        add("llm.prefill.chunk.2048", "tpu_custom_call.4", 2.5e-2,
            "sparse_attn/sparse_prefill")
        add("llm.prefill.chunk.2048", "copy.3", 1e-3, "")
    return {"window": [1.0, t + 1.0],
            "events": {"/device:TPU:0": events}, "modules": {}}


def _ptrace(joined, steps=3, chunks=2):
    """The program's own spans: a pull a decode step and a chunk span a
    chunk ending inside the window, and one of each outside it."""
    start, end = joined["window"]
    spans = [["llm.step", start - 0.6, end - start + 2.0],
             ["llm.decode.pull", start - 0.5, 1e-3],
             ["llm.prefill.chunk", end + 0.5, 0.2]]
    spans += [["llm.decode.pull", start + 0.01 * (i + 1), 1e-3]
              for i in range(steps)]
    spans += [["llm.prefill.chunk", start + 0.1 * (i + 1), 0.05]
              for i in range(chunks)]
    return {"window": joined["window"], "ops": {},
            "spans": {"loop#0": sorted(spans, key=lambda s: s[1])}}


@pytest.fixture
def traced(monkeypatch):
    import jax

    from perfbench import op_scopes, program_trace
    joined = _joined()
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: joined)
    monkeypatch.setattr(program_trace, "of_run",
                        lambda facts: _ptrace(joined))
    monkeypatch.setattr(jax, "devices", lambda: [
        type("D", (), {"device_kind": "TPU v5 lite"})()])
    return joined


def _value(name, facts=None):
    spec = manifest.metric_spec("per_layer", name)
    return manifest.reducer(spec["reducer"])(
        {} if facts is None else facts, spec["params"])


def test_a_scopes_time_is_read_per_span_of_its_own_program(traced):
    assert _value("sparse.decode_select_ms") == pytest.approx(4 * 0.1)
    # the kernel and the layout op before it
    assert _value("sparse.decode_attn_ms") == pytest.approx(4 * 0.06)
    # the mixer's projections and its state step
    assert _value("lightning.decode_mixer_ms") == pytest.approx(0.5)
    # a chunk's operations over the chunks enqueued in the window; the
    # decode program's scopes of the same name are another program's
    assert _value("lightning.prefill_scan_ms") == pytest.approx(5.0)
    assert _value("sparse.prefill_select_ms") == pytest.approx(30.0)
    assert _value("sparse.prefill_attn_ms") == pytest.approx(25.0)


def test_the_shares_of_the_memory_roofline_stay_under_100(traced,
                                                          monkeypatch):
    from perfbench.reducers import decode_expert_hbm_share
    # the state: 2 x 4 rows x 25,165,824 B over 0.2 ms a step
    share = _value("lightning.decode_state_hbm_share")
    assert share == pytest.approx(100 * 8 * 25_165_824 / 2e-4 / 819e9)
    # a made-up step that fast is over the roofline: the test of the
    # arithmetic, not of a chip
    assert share > 100
    # the pages: 3 steps of 4 rows x 4 layers x 2 heads x 64 pages, over
    # the kernel's 3 x 4 x 0.05 ms alone (not the layout op)
    pages = 3 * 4 * 4 * 2 * 64
    monkeypatch.setattr(decode_expert_hbm_share, "attribute_sum",
                        lambda facts, params, window: (3, pages))
    facts = {"notes": {}}
    assert _value("sparse.decode_attn_hbm_share", facts) == pytest.approx(
        100 * pages * 65_536 / (3 * 4 * 5e-5) / 819e9)
    assert facts["notes"]["decode_pages_hbm"] == {
        "steps": 3, "pages_read": pages,
        "kernel_seconds": pytest.approx(6e-4), "bytes": pages * 65_536}
    spec = manifest.metric_spec("per_layer", "sparse.decode_attn_hbm_share")
    assert (spec["params"]["span"], spec["params"]["attribute"]) \
        == ("llm.decode.pull", "sparse_pages_read")


def test_the_prefill_kernels_share_counts_what_the_selection_requires(
        traced, monkeypatch):
    from perfbench.reducers import prefill_sparse_peak_share as share
    spec = manifest.metric_spec("per_layer", "sparse.prefill_attn_peak_share")
    params = spec["params"]
    assert (params["span"], params["chunk"], params["names"]) \
        == ("llm.prefill.chunk", 2048, ["custom"])
    start, end = traced["window"]
    events = [_Event("llm.prefill.chunk", start + 0.1, 0.05, chunk=30,
                     tokens=65344),
              _Event("llm.prefill.chunk", start + 0.2, 0.05, chunk=31,
                     tokens=65344),
              _Event("llm.prefill.chunk", end + 0.5, 0.05, chunk=0,
                     tokens=65344),                 # ends after the window
              _Event("llm.decode.pull", start + 0.1, 1e-3, step=3)]
    line = type("L", (), {"name": "python", "events": events})()
    planes = [type("P", (), {"name": "/host:CPU", "lines": [line]})()]
    assert share.listed(planes, params, traced["window"]) \
        == [(30, 65344), (31, 65344)]
    monkeypatch.setattr(share, "chunks_in", lambda facts, p, window:
                        share.listed(planes, p, window))
    facts = {"notes": {}}
    config = manifest.load_cell(manifest.load_manifest(), CELL)["config_file"]
    flops = sum(sala_bytes.chunk_required_attention_flops(
        config, i * 2048, 65344, 2048) for i in (30, 31))
    # over the kernel's 2 x 25 ms of the made-up window alone
    assert share.reduce(facts, params) == pytest.approx(
        100 * flops / 5e-2 / 197e12)
    assert 0 < share.reduce(facts, params) < 100
    assert facts["notes"]["prefill_sparse_peak"]["chunks"] == 2
    monkeypatch.setattr(share, "chunks_in", lambda facts, p, window: [])
    assert share.reduce({}, params) is None


class _Event:
    def __init__(self, name, start_s, dur_s, **stats):
        self.name, self.stats = name, list(stats.items())
        self.start_ns, self.duration_ns = start_s * 1e9, dur_s * 1e9


def test_every_new_metric_reads_nothing_where_there_is_nothing(monkeypatch):
    """The parent's program: no capture, no op map, no span, no counter.
    Each reducer returns None and raises nothing."""
    from perfbench import op_scopes, program_trace
    from ray_tpu.util import metrics
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: None)
    monkeypatch.setattr(program_trace, "of_run", lambda facts: None)
    monkeypatch.setattr(metrics, "registry_snapshot", lambda: {})
    for name in MINE:
        assert _value(name, {"trace": None, "notes": {}}) is None, name
    # a capture of a program that has none of the scopes or spans
    bare = {"window": [0.0, 1.0], "modules": {}, "events": {
        "/device:TPU:0": [["llm.decode.32", "fusion.1", 0.1, 1e-3,
                           _entry("step", "mlp")]]}}
    monkeypatch.setattr(op_scopes, "of_run", lambda facts: bare)
    monkeypatch.setattr(program_trace, "of_run", lambda facts: {
        "window": [0.0, 1.0], "ops": {},
        "spans": {"loop#0": [["llm.step", 0.1, 0.5],
                             ["llm.decode.pull", 0.2, 1e-3]]}})
    for name in MINE:
        assert _value(name, {"trace": None, "notes": {}}) is None, name


def test_the_pages_read_share_is_the_ratio_of_the_programs_counters(
        monkeypatch):
    from ray_tpu.util import metrics

    def snapshot():
        return {"rtpu_llm_sparse_pages_read": {"series": [
                    {"value": 300.0}, {"value": 212.0}]},
                "rtpu_llm_sparse_pages_held": {"series": [
                    {"value": 4096.0}]}}
    monkeypatch.setattr(metrics, "registry_snapshot", snapshot)
    assert _value("sparse.decode_pages_read_share") \
        == pytest.approx(100 * 512 / 4096)
    spec = manifest.metric_spec("per_layer", "engine.prefill_chunk_ms")
    assert spec["reducer"] == "program_span_ms" \
        and spec["params"]["spans"] == ["llm.prefill.chunk"]
