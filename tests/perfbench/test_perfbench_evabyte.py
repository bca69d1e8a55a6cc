"""The ``evabyte`` family in the harness: its configuration file against the
program's preset and the catalog, the manifest's entries BY NAME, the
traffic file against the issue's numbers and under the 402 seeds of
``test_perfbench_traffic.py``, ``bytes_evabyte.py`` / ``flops_evabyte.py``
against hand counts, and the serving job itself on ``llama:tiny-eva`` (a
``--rehearse`` of the cell runs the toy GPT-2, which folds nothing, so the
family's own model goes through the job here, at the sizes of
``rehearsal/evabyte.json``): its check crosses a close in the prompt's
chunks and one in decode and passes, and fails on a fold broken in the
program, on one broken in the reference, and on float8 weights in the
reference.

The tiny model computes in float32, so its sound runs read logit
differences of 2e-6 to 4e-6; the limit here is 1e-3.
"""

import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import bytes_evabyte, bytes_evabyte_fold, flops_evabyte, \
    manifest
from perfbench.families import evabyte as family

CELL = "evabyte-6.5b.serve-bytes-longfile"
CONFIG = "evabyte-6.5b"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MINE = ("eva.decode_attn_ms", "eva.decode_attn_hbm_share", "eva.fold_ms",
        "eva.fold_hbm_share", "eva.prefill_attn_ms",
        "eva.prefill_attn_peak_share", "eva.rows_read_share",
        "eva.blocks_held_share")
SHARED = ("engine.ttft_p50_ms", "engine.first_token_p50_ms",
          "engine.token_gap_p50_ms", "engine.token_gap_p95_ms",
          "engine.prefill_chunk_ms", "engine.compiles_in_window",
          "scheduler.batch_occupancy", "scheduler.preemptions",
          "scheduler.queue_wait_mean_ms", "device.idle_unoffered_share",
          "device.idle_with_work_share", "device.idle_per_prefill_ms")
LIMITS = {"logit_atol": 1e-3, "why_logit_atol": "float32 against float32"}


def _check_module():
    spec = importlib.util.spec_from_file_location(
        "evabyte_check", manifest.ROOT / "benchmarks" / "evabyte_check.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("evabyte_check", mod)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


# ------------------------------------------------ the files and the manifest
def test_the_configuration_is_the_programs_preset(cell):
    from ray_tpu.models import llama
    config = cell["config_file"]
    preset = llama.PRESETS["evabyte-6.5b-l8"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] == "llama:evabyte-6.5b-l8"
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert "four pipeline stages of eight" in config["deployment"]
    assert "0.7%" in config["distorts"] and len(config["assumed"]) >= 8
    for word in ("ICLR 2023", "when the window closes", "head 0",
                 "mixedp_attn", "RoPE over halves", "clipped to +-1",
                 "bf16", "float32"):
        assert any(word in rule for rule in config["assumed"]), word
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["vocab_size"], config["max_position_embeddings"],
            config["rope_theta"], config["window_size"],
            config["chunk_size"], config["num_pred_heads"]) \
        == (4096, 32, 32, 11008, 320, 32768, 100000, 2048, 16, 8)
    assert (preset.eva_window, preset.eva_chunk, preset.prefill_chunk,
            preset.pred_heads, preset.norm_offset, preset.residual_f32) \
        == (2048, 16, 2048, 8, True, True)
    assert family.routed(config) is None and family.stepping(config) is None
    with pytest.raises(ValueError, match="chunk_size"):
        family.check_sizes({**config, "chunk_size": 32}, preset)
    with pytest.raises(ValueError, match="num_pred_heads"):
        family.check_sizes({**config, "num_pred_heads": 1}, preset)
    with pytest.raises(ValueError, match="attention_class"):
        family.check_sizes({**config, "attention_class": "softmax"}, preset)
    serve = config["serve"]
    assert serve["logit_atol"] > 0 and "chip" in serve["why_logit_atol"]
    engine = serve["engine"]
    assert (engine["max_num_seqs"], engine["decode_batch_buckets"],
            engine["num_blocks"], engine["block_size"],
            engine["max_model_len"], engine["prefill_len_buckets"]) \
        == (8, [8], 448, 64, 26624, [8192, 16384, 26624])
    # every slot at its worst on the way to max_context: 12 closed windows
    # at 2 pages and the open one's 32; nothing is preempted for room
    worst = bytes_evabyte.pages_at_most(config, engine["max_model_len"],
                                        engine["block_size"])
    assert worst == 56
    assert engine["num_blocks"] == engine["max_num_seqs"] * worst
    assert all(b % preset.prefill_chunk == 0
               for b in engine["prefill_len_buckets"])


def test_every_number_of_the_catalog_is_in_the_file(cell):
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows
               if r["source_url"] == cell["config_file"]["source"])
    assert row["name"] == "EvaByte"
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
        == (CONFIG, "serve-bytes-longfile", 1)
    assert len(mine["why"]) <= 200
    assert len([w for w in bench["workloads"]
                if w["config"] == CONFIG]) == 1
    # no token gap end to end: a chunk lies in the gap
    reported = {m["name"] for m in
                manifest.metrics_of_cell(bench, "end_to_end", CELL)}
    assert reported == {"serve_out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in
             manifest.metrics_of_cell(bench, "per_layer", CELL)}
    assert set(layer) >= set(MINE) | set(SHARED)
    for name in MINE:
        m = manifest.find(bench["per_layer"], name, "metric")
        assert m["workloads"] == [CELL] \
            and m["moves"] == "serve_out_tokens_per_s" \
            and m["layer"] == "eva"
        spec = manifest.metric_spec("per_layer", name)
        assert (spec["layer"], spec["unit"], spec["better"],
                spec["source"], spec["moves"]) \
            == (m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        manifest.reducer(spec["reducer"])
    for name in SHARED:
        assert CELL in manifest.find(bench["per_layer"], name,
                                     "metric")["workloads"]
    assert all(m["moves"] in reported for m in layer.values())
    assert all("workloads" in m for m in bench["per_layer"])


def test_the_traffic_is_the_issues(cell):
    from perfbench import traffic
    spec = cell["traffic_file"]
    assert spec["kind"] == "serve"
    assert spec["prompt_tokens"] == {"median": 8192, "sigma": 0.6,
                                     "lo": 2048, "hi": 24576}
    assert spec["output_tokens"] == {"median": 768, "sigma": 0.6,
                                     "lo": 256, "hi": 2048}
    assert (spec["prompt_quantiles"], spec["output_quantiles"]) == (4, 3)
    grid = traffic.length_grid(spec)
    prompts = sorted({p for p, _ in grid})
    outputs = sorted({o for _, o in grid})
    assert prompts == [4108, 6766, 9918, 16336]
    assert outputs == [430, 768, 1372] and len(grid) == 12
    assert sum(o for _, o in grid) == 10280
    # every prompt closes a window in its chunks, and a request in three
    # closes one in decode (an output over the 2,048 positions of a window)
    assert min(prompts) > 2048
    assert (spec["ttft_limit_s"], spec["itl_limit_s"],
            spec["check_prompt_tokens"], spec["check_decode_steps"],
            spec["max_context"]) == (15.0, 0.5, 4090, 8, 26624)
    # the check's sixth step's byte closes the second window in decode
    assert spec["check_prompt_tokens"] + 6 == 2 * 2048
    assert max(p + o for p, o in grid) <= spec["max_context"]
    knee = spec["knee"]
    window = json.loads((manifest.ROOT / "BENCHMARK.json")
                        .read_text())["run_seconds"]
    cycles = window / spec["cycle_seconds"]
    assert cycles == int(cycles) == knee["k"]
    warm = spec["warm_seconds"] / spec["cycle_seconds"]
    assert warm == int(warm) and warm >= 1
    # ISSUE 52's rule: the largest whole k that offers at most 0.78 of the
    # knee, which lands in the issue's band
    share = traffic.rate_rps(spec) / knee["knee_rps"]
    assert 0.60 <= share <= 0.78
    assert share == pytest.approx(knee["share_of_knee"], abs=1e-3)
    assert len(grid) * (knee["k"] + 1) / window > 0.78 * knee["knee_rps"]
    assert knee["offered_tokens_per_s"] == pytest.approx(
        sum(o for _, o in grid) / spec["cycle_seconds"], abs=0.01)
    assert "three sweeps" in knee["found"] and knee["offered"]
    engine = cell["config_file"]["serve"]["engine"]
    assert engine["prefill_len_buckets"][-1] >= engine["max_model_len"] \
        == spec["max_context"]


def test_every_seed_has_arrivals_in_the_traced_stretch(cell):
    """``test_perfbench_traffic.py``'s rule, on this cell's file by name."""
    from perfbench import traffic
    spec = cell["traffic_file"]
    seconds = json.loads((manifest.ROOT / "BENCHMARK.json")
                         .read_text())["run_seconds"]
    period = traffic.cycle_seconds(spec)
    lead = seconds - min(spec["trace_seconds"], seconds)
    n = len(traffic.length_grid(spec))
    for seed in list(range(400)) + [798041194, 2 ** 31 + 11]:
        due = np.sort(traffic.rng_for(seed, "serve_arrivals")
                      .uniform(0.0, period, n))
        times = np.concatenate([k * period + due for k in
                                range(int(seconds // period) + 1)])
        inside = (times >= lead + 1.0) & (times < seconds - 1.0)
        assert inside.any(), seed


def test_the_bytes_and_operations_are_the_hand_counts(cell):
    config = cell["config_file"]
    # K and V: 32 heads x 128 float32 lanes each
    assert bytes_evabyte.row_bytes(config) == 2 * 32 * 128 * 4 == 32768
    assert bytes_evabyte.page_bytes(config) == 32768
    assert bytes_evabyte.folded_rows(config) == 128
    # at 26,624 - 1 positions: 12 closed windows and 2,047 of the 13th
    assert bytes_evabyte.held_rows(config, 26623) == 12 * 128 + 2047
    assert bytes_evabyte.held_rows(config, 26624) == 13 * 128
    assert bytes_evabyte.pages_at_most(config, 26624, 64) == 56
    assert bytes_evabyte.pages_at_most(config, 2048, 64) == 32
    assert bytes_evabyte.pages_at_most(config, 2049, 64) == 32
    assert bytes_evabyte.pages_at_most(config, 4095, 64) == 2 + 32
    # a window's fold in a layer: 2,048 rows read, 128 written
    assert bytes_evabyte.fold_bytes(config) == (2048 + 128) * 32768
    assert bytes_evabyte_fold.page_bytes(config) == 34816
    assert bytes_evabyte.layer_weight_bytes(config) \
        == (4 * 4096 * 4096 + 3 * 4096 * 11008) * 2
    # q . k and p v over 128 lanes in 32 heads, a multiply and an add each
    assert flops_evabyte.pair_flops(config) == 4 * 32 * 128 == 16384
    assert flops_evabyte.unit_flops(config) == 16384
    from perfbench.reducers import attribute_ratio
    assert attribute_ratio.reduce({}, {"span": "llm.decode.pull",
                                       "numerator": "rows_read",
                                       "denominator": "positions_seen"}) \
        is None


def test_a_shrunk_configuration_is_handed_to_the_gpt2_family(cell):
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    shrunk = {**cell["config_file"], **over["config"]}
    assert family.shrunk(shrunk) and not family.shrunk(cell["config_file"])
    from ray_tpu.models import gpt2
    family.check_sizes(shrunk, gpt2.PRESETS["tiny"]())


# --------------------------------------------------- the job on the family
def _tiny_ctx(seed: int) -> dict:
    """The job's context as run.prepare builds it, for llama:tiny-eva."""
    from ray_tpu.models import llama
    tiny = llama.PRESETS["tiny-eva"]()
    toy = json.loads((manifest.BENCH_DIR / "rehearsal" / "evabyte.json")
                     .read_text())
    config = {"family": "evabyte", **family.FIXED,
              **{k: getattr(tiny, attr) for k, attr in family.KEYS.items()},
              "serve": {"engine": toy["serve_engine"], **LIMITS}}
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    spec = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-bytes-longfile.json").read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **over["traffic"]["serve"],
                             "check_prompt_tokens":
                                 toy["check_prompt_tokens"],
                             "check_decode_steps": toy["check_decode_steps"]},
            "seed": seed, "seconds": 1.0, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def test_the_serving_job_runs_the_family_and_its_check_crosses_a_close():
    """Served(ctx) -> the window -> check_logits through the job's OWN
    stepping: a prompt of 58 (one window folded by its chunk, 26 positions
    open), 8 decode steps of which the sixth's byte closes the second
    window inside ``cache.append_slot``; and the counters' metric reads a
    number."""
    from perfbench.jobs import serve
    from perfbench.reducers import catalog_counter_ratio
    facts = serve.run(_tiny_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0, facts["compared"]
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    assert facts["wrong_length"] == 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    spec = manifest.metric_spec("per_layer", "eva.blocks_held_share")
    held = catalog_counter_ratio.reduce(facts, spec["params"])
    assert held is not None and 0 < held <= 100


@pytest.mark.parametrize("fault", ["uniform_a", "no_mu"])
def test_the_jobs_check_fails_a_fold_broken_in_the_program(fault):
    from perfbench.jobs import serve
    with _check_module().broken(fault):
        served = serve.Served(_tiny_ctx(seed=4))
        try:
            folded = served.eng.cache.windows_folded
            check = served.check_logits(4)
            assert served.eng.cache.windows_folded == folded + 1
        finally:
            served.close()
    assert not check["ok"]
    assert max(check["prefill_logit_diff"], check["decode_logit_diff"]) \
        > 30 * check["logit_atol"]


def test_the_check_fails_on_broken_references_and_float8_weights():
    """One stepping of the program, judged against the sound reference, the
    reference under each fault of ``evabyte_ref.FAULTS`` and under float8
    weights, as ``benchmarks/evabyte_check.py`` does on the chip."""
    from perfbench import traffic
    from perfbench.jobs import serve
    from perfbench.reference import evabyte_ref
    check = _check_module()
    served = serve.Served(_tiny_ctx(seed=6))
    try:
        spec = served.spec
        prompt = [int(t) for t in traffic.rng_for(6, "serve_check").integers(
            0, served.config["vocab_size"], spec["check_prompt_tokens"])]
        compared = served.stepping.check(served, prompt,
                                         spec["check_decode_steps"])
        sound = check.judged(served, compared)
        from ray_tpu.models import llama
        low = check.rounded_to_float8(served.params, llama.WIDE_PARAMS)
        control = check.judged(served, compared, params=low)
        faults = {fault: check.judged(served, compared, fault=fault)
                  for fault in evabyte_ref.FAULTS}
    finally:
        served.close()
    assert sound["ok"] and not control["ok"]
    assert control["prefill_logit_diff"] > 30 * sound["logit_atol"]
    for fault, verdict in faults.items():
        assert not verdict["ok"], fault
        assert max(verdict["prefill_logit_diff"],
                   verdict["decode_logit_diff"]) \
            > 30 * sound["logit_atol"], fault
