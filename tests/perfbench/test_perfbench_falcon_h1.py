"""The Falcon-H1 family in the harness: its configuration file against
the program's preset and the catalog, the serving job itself on
``falcon_h1:tiny`` (the rehearsal of the cell runs the toy GPT-2, so the
family's own model goes through the job here), the bytes of state a step
moves, and the two reducers on a made-up trace."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import bytes_falcon_h1, manifest
from perfbench.families import falcon_h1 as family
from perfbench.reducers import decode_state_hbm_share, ops_ms_in_span

CELL = "falcon-h1-34b.serve-chat-busy"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


def test_the_configuration_is_the_programs_preset(cell):
    from ray_tpu.models import falcon_h1
    config = cell["config_file"]
    preset = falcon_h1.PRESETS["falcon-h1-34b-l6"]()
    family.check_sizes(config, preset)
    assert config["serve"]["engine"]["model"] == "falcon_h1:falcon-h1-34b-l6"
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 72}
    for key in ("deployment", "distorts", "assumed"):
        assert config[key]
    with pytest.raises(ValueError, match="mamba_d_state"):
        family.check_sizes({**config, "mamba_d_state": 128}, preset)
    with pytest.raises(ValueError, match="attention_bias"):
        family.check_sizes({**config, "attention_bias": True}, preset)


def test_every_number_of_the_catalog_is_in_the_file(cell):
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    rows = [json.loads(x) for x in CATALOG.read_text().splitlines()]
    row = next(r for r in rows if r["source_url"] == cell["config_file"]["source"])
    differ = {k for k, v in row["config"].items()
              if cell["config_file"].get(k, "absent") != v}
    assert differ == set(cell["config_file"]["reduced"])


def test_a_shrunk_configuration_is_handed_to_the_gpt2_family(cell):
    """What --rehearse makes of the cell: GPT-2's names present."""
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    shrunk = {**cell["config_file"], **over["config"]}
    assert family.shrunk(shrunk) and not family.shrunk(cell["config_file"])
    from ray_tpu.models import gpt2
    family.check_sizes(shrunk, gpt2.PRESETS["tiny"]())
    with pytest.raises(ValueError):
        family.check_sizes(shrunk, gpt2.PRESETS["gpt2-124m"]())


def _tiny_ctx(seed: int) -> dict:
    """The job's context as run.prepare builds it, for falcon_h1:tiny."""
    from ray_tpu.models import falcon_h1
    tiny = falcon_h1.PRESETS["tiny"]()
    config = {"family": "falcon_h1", **family.FIXED,
              **family.sizes_of_model(tiny),
              "serve": {"engine": {
                  "model": "falcon_h1:tiny", "max_model_len": 64,
                  "max_num_seqs": 4, "num_blocks": 32, "block_size": 8,
                  "max_prefill_tokens": 64,
                  "prefill_len_buckets": [16, 32, 64],
                  "decode_batch_buckets": [4], "share_weights": False},
                  # bf16 activations through two tiny layers: 0.04-0.05 seen
                  # (tests/test_falcon_h1.py), three times that
                  "logit_atol": 0.15}}
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    spec = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-chat-busy.json").read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **over["traffic"]["serve"],
                             "check_prompt_tokens": 21,
                             "check_decode_steps": 6},
            "seed": seed, "seconds": 1.0, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def test_the_serving_job_runs_the_family_and_its_check_passes():
    """Served(ctx) -> the window -> check_logits, with today's calls:
    prefill, scatter_prefill, append_slot, decode with a table and no
    sequence id, write_token.  The state row travels behind them."""
    from perfbench.jobs import serve
    ctx = _tiny_ctx(seed=2 ** 31 + 5)
    facts = serve.run(ctx)
    assert facts["correct"] and facts["failed"] == 0
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]


def test_the_check_fails_on_a_neighbours_state(monkeypatch):
    """The comparison can fail: decode steps that read another row's
    state (here: always the last row, which nobody wrote) are outside the tolerance."""
    from perfbench.jobs import serve
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    monkeypatch.setattr(
        PagedKVCache, "rows_of",
        lambda self, tables: np.full(len(tables), self.state_rows - 1,
                                     np.int32))
    served = serve.Served(_tiny_ctx(seed=11))
    try:
        check = served.check_logits(11)
    finally:
        served.close()
    assert check["prefill_logit_diff"] < check["logit_atol"]
    assert check["decode_logit_diff"] > 2 * check["logit_atol"]
    assert not check["ok"]


def test_bytes_of_state_a_step_moves(cell):
    sizes = family.sizes(cell["config_file"])
    # a layer: 32 x 128 x 256 scan state + 3 x 5,120 conv tail, float32
    assert bytes_falcon_h1.state_bytes_per_row(sizes) \
        == 6 * (32 * 128 * 256 + 3 * 5120) * 4 == 25_534_464
    assert bytes_falcon_h1.decode_state_bytes(sizes, 32) == 2 * 32 * 25_534_464


def _trace():
    """Two decode spans of 10 ms; in each the store's two fusions (2 + 1
    ms) and a matmul that is not the store's; one stray store op outside
    any span."""
    ops = []
    for start in (1.000, 1.020):
        ops += [["select_dynamic-update-slice_fusion.2 f32[6,33,32,128,256]",
                 start + 0.001, 0.002],
                ["slice_multiply_fusion.2 f32[33,32,128]", start + 0.004,
                 0.001],
                ["fusion.77 bf16[32,21504]", start + 0.006, 0.003]]
    ops.append(["select_dynamic-update-slice_fusion.2 f32[6,33,32,128,256]",
                1.045, 0.002])
    host = [["pb.decode", 1.000, 0.010], ["pb.decode", 1.020, 0.010],
            ["pb.prefill", 1.040, 0.010]]
    return {"device": {"/device:TPU:0": ops}, "device_async": {},
            "host": host, "window": [1.0, 1.05]}


def test_state_reducers_on_a_made_up_trace(monkeypatch):
    spec = manifest.metric_spec("per_layer", "ssm.decode_state_ms")
    facts = {"trace": _trace()}
    ms = ops_ms_in_span.reduce(facts, spec["params"])
    assert ms == pytest.approx(3.0)
    share_spec = manifest.metric_spec("per_layer",
                                      "ssm.decode_state_hbm_share")
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [
        type("D", (), {"device_kind": "TPU v5 lite"})()])
    share = decode_state_hbm_share.reduce(facts, share_spec["params"])
    assert share == pytest.approx(
        100 * 2 * 32 * 25_534_464 / 3.0e-3 / 819e9)
    # a program without the operations, and a run without a trace
    bare = {"trace": {**_trace(), "device": {"/device:TPU:0": [
        ["fusion.77 bf16[32,21504]", 1.006, 0.003]]}}}
    for reducer, s in ((ops_ms_in_span, spec),
                       (decode_state_hbm_share, share_spec)):
        assert reducer.reduce(bare, s["params"]) is None
        assert reducer.reduce({"trace": None}, s["params"]) is None
