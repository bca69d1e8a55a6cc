"""Device time by scope (perfbench/op_scopes.py) and the two reducers that
read it, on made-up planes and maps whose answers are known: two programs
that share an instruction name, prefill buckets that share a module name,
an operation of the scatter program inside a decode span."""

import json

import pytest

from perfbench import manifest, op_scopes, program_trace
from perfbench.reducers import scope_ms_in_program_span, scope_ms_per_step

NEW_METRICS = {
    "train_program.optimizer_ms": ["optimizer"],
    "train_program.head_loss_ms": ["lm_head", "loss_ce"],
    "train_program.attn_scope_ms": ["attn"],
    "train_program.mlp_ms": ["mlp"],
    "moe.dispatch_ms": ["moe_dispatch", "moe_combine"],
    "moe.experts_scope_ms": ["moe_experts"],
    "train_program.unscoped_ms": None,
    "model_step.decode_mlp_ms": ["mlp"],
    "model_step.decode_head_ms": ["lm_head", "embed"],
    "model_step.decode_attn_ms": ["attn_qkv", "rope", "qk_norm", "attn",
                                  "paged_attention", "attn_out"],
    "ssm.decode_mixer_ms": ["ssm_in", "ssm_conv", "ssm_step", "ssm_norm",
                            "ssm_out"],
}
TRAIN = ["gpt2-xl-1558m.train-b8-s1024", "olmoe-1b-7b.train-b2-s4096",
         "kanana-2-30b-a3b.train-b2-s8192"]
SERVE = ["gpt2-xl-1558m.serve-chat-steady", "falcon-h1-34b.serve-chat-busy"]


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = \
            name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _entry(scope, shape, pass_="", **more):
    return {"scope": scope, "path": scope, "pass": pass_, "prim": "dot_general",
            "src": "model.py:1", "shape": shape, **more}


def _op(name, shape, opcode="fusion"):
    return f"%{name} = {shape}{{0}} {opcode}(%p)"


# ----------------------------------------------------------- a serving capture
# times in microseconds; the window is 0-100.  Two decode steps (10-30,
# 50-70), a prefill of bucket 64 before the first and its scatter, which
# the device runs INSIDE the second decode span (behind the enqueue).
# fusion.3 exists in all four programs with another meaning in each.
SERVE_MAPS = {
    "llm.decode.8": {"module": "jit_decode_step", "ops": {
        "fusion.3": _entry("mlp", "bf16[8,64]"),
        "fusion.4": _entry("lm_head", "f32[8,256]"),
        "tpu_custom_call.1": _entry("attn/paged_attention", "bf16[8,1,64]"),
        "fusion.9": _entry("", "s32[]")}},
    "llm.prefill.64": {"module": "jit_prefill_step", "ops": {
        "fusion.3": _entry("attn_qkv", "bf16[1,64,192]"),
        "fusion.5": _entry("mlp", "bf16[1,64,256]")}},
    "llm.prefill.128": {"module": "jit_prefill_step", "ops": {
        "fusion.3": _entry("attn_qkv", "bf16[1,128,192]"),
        "fusion.5": _entry("mlp", "bf16[1,128,256]")}},
    "llm.prefill.scatter.64": {"module": "jit__scatter_prefill", "ops": {
        "fusion.3": _entry("kv_write", "f32[512,64]")}},
}


def _us(name, start, dur):
    return _Ev(name, start * 1000, dur * 1000)


@pytest.fixture
def serve_planes():
    return [
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [
                _us("jit_prefill_step(11)", 2, 6),
                _us("jit_decode_step(7)", 12, 16),
                _us("jit__scatter_prefill(13)", 52, 4),
                _us("jit_decode_step(7)", 56, 12),
                _us("jit__logits_row(21)", 80, 2)]),
            _Line("XLA Ops", [
                _us(_op("fusion.3", "bf16[1,64,192]"), 2, 2),
                _us(_op("fusion.5", "bf16[1,64,256]"), 4, 4),
                _us(_op("fusion.3", "bf16[8,64]"), 12, 6),
                _us(_op("tpu_custom_call.1", "bf16[8,1,64]",
                        "custom-call"), 18, 2),
                _us(_op("fusion.4", "f32[8,256]"), 20, 4),
                _us(_op("fusion.9", "s32[]"), 24, 1),
                _us(_op("fusion.3", "f32[512,64]"), 52, 4),
                _us(_op("fusion.3", "bf16[8,64]"), 56, 6),
                _us(_op("while.2", "(s32[], bf16[8,64])", "while"), 56, 12),
                _us(_op("fusion.4", "f32[8,256]"), 62, 4),
                _us(_op("fusion.3", "f32[8]"), 80, 2)])]),
        _Plane("/host:CPU", [
            _Line("python", [
                _us("pb.window", 0, 100),
                _us("llm.step", 1, 8), _us("llm.prefill", 1, 8),
                _us("llm.step", 10, 20), _us("llm.decode", 10, 20),
                _us("llm.step", 50, 20), _us("llm.decode", 50, 20)])]),
    ]


def _facts(**more):
    return {"trace": {"window": [0.0, 1e-4]}, "notes": {}, **more}


@pytest.fixture
def joined_serve(serve_planes, monkeypatch):
    """of_run over the made-up capture, as a run's reducers call it."""
    class _Data:
        planes = serve_planes
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _Data))
    monkeypatch.setattr(program_trace, "newest_capture",
                        lambda scratch=None: "made-up.xplane.pb")
    monkeypatch.setattr(op_scopes, "program_maps", lambda: SERVE_MAPS)
    monkeypatch.setattr(op_scopes, "_LOADED", {})
    monkeypatch.setattr(program_trace, "_LOADED", {})
    return _facts()


def test_an_operation_is_looked_up_in_the_map_of_its_own_module(
        joined_serve):
    joined = op_scopes.of_run(joined_serve)
    assert joined["modules"] == {
        "jit_prefill_step(11)": "llm.prefill.64",      # by its shapes
        "jit_decode_step(7)": "llm.decode.8",
        "jit__scatter_prefill(13)": "llm.prefill.scatter.64",
        "jit__logits_row(21)": ""}                     # nobody registered it
    events = joined["events"]["/device:TPU:0"]
    got = [(program, name, entry and entry["scope"])
           for program, name, _, _, entry in events if name == "fusion.3"]
    assert got == [("llm.prefill.64", "fusion.3", "attn_qkv"),
                   ("llm.decode.8", "fusion.3", "mlp"),
                   ("llm.prefill.scatter.64", "fusion.3", "kv_write"),
                   ("llm.decode.8", "fusion.3", "mlp"),
                   ("", "fusion.3", None)]
    assert not [e for e in events if e[1].startswith("while")]  # a wrapper


def _span_ms(facts, scopes, program="llm.decode"):
    return scope_ms_in_program_span.reduce(
        facts, {"program": program, "span": "llm.decode", "scopes": scopes})


def test_decode_scopes_count_the_decode_programs_operations_only(
        joined_serve):
    """fusion.3 of the scatter program runs inside the second llm.decode
    span and is not the decode program's mlp: (6 + 6) us over 2 spans."""
    assert _span_ms(joined_serve, ["mlp"]) == pytest.approx(6e-3)
    assert _span_ms(joined_serve, ["lm_head"]) == pytest.approx(4e-3)
    assert _span_ms(joined_serve, ["attn", "attn_qkv"]) == \
        pytest.approx(1e-3)                 # the kernel, once in two spans
    assert _span_ms(joined_serve, ["kv_write"]) == 0.0
    assert _span_ms(joined_serve, ["kv_write"],
                    program="llm.prefill.scatter") == pytest.approx(2e-3)
    # the scopes of one decode step add up to its device time but the
    # unscoped fusion.9 (1 us in the first span) and the scatter's 4
    total = sum(_span_ms(joined_serve, [s]) for s in
                ("mlp", "lm_head", "paged_attention"))
    assert total == pytest.approx(1e-3 * (12 + 8 + 2) / 2)


def test_the_summary_lands_in_the_runs_notes(joined_serve):
    op_scopes.of_run(joined_serve)
    note = joined_serve["notes"]["op_scopes"]
    assert note["matched_share"] == pytest.approx(1 - 2 / 35)
    by = note["seconds_by_program_scope_pass"]
    assert by["llm.decode.8|mlp|"] == pytest.approx(12e-6)
    assert by["llm.prefill.scatter.64|kv_write|"] == pytest.approx(4e-6)
    assert [row[0] for row in note["largest_unscoped"]] == [
        "|fusion.3", "llm.decode.8|fusion.9"]
    json.dumps(note)                        # the notes are a JSON line


@pytest.mark.parametrize("facts", [
    {"trace": None}, {}, {"trace": {"window": [0, 1]}}])
def test_nothing_to_read_gives_none_and_builds_no_map(facts, monkeypatch):
    """An untraced run never asks the program for its maps; a traced one
    whose capture is gone neither."""
    def never():
        raise AssertionError("op_maps() was called")
    monkeypatch.setattr(op_scopes, "program_maps", never)
    monkeypatch.setattr(program_trace, "newest_capture",
                        lambda scratch=None: None)
    assert op_scopes.of_run(facts) is None
    assert scope_ms_per_step.reduce({**facts, "steps": 3}, {}) is None
    assert scope_ms_in_program_span.reduce(facts, {}) is None


def test_a_program_without_op_maps_has_nothing_to_report(monkeypatch):
    """The parent of the PR that added the maps: the reader returns None
    and does not raise."""
    from ray_tpu.util import tracing
    monkeypatch.delattr(tracing, "op_maps")
    assert op_scopes.program_maps() is None
    monkeypatch.undo()
    monkeypatch.setattr(tracing, "_PROGRAMS", {})
    assert op_scopes.program_maps() is None     # nothing registered


# ---------------------------------------------------------- a training capture
def test_scope_ms_per_step_is_the_share_of_the_window_times_the_step():
    """40 us traced, two devices; the step takes 10 ms on the host clock."""
    maps = {"train.step": {"module": "jit__step", "ops": {
        "fusion.1": _entry("mlp", "bf16[8]", "fwd"),
        "fusion.2": _entry("mlp", "bf16[8]", "bwd", mixed=["ln_2", "mlp"]),
        "fusion.3": _entry("grads/lm_head", "bf16[8]", "fwd"),
        "fusion.4": _entry("optimizer", "bf16[8]"),
        "fusion.5": _entry("grads", "bf16[8]", "fwd"),
        "copy.6": _entry("", "bf16[8]", "bwd")}}}

    def plane(scale):
        ops = [("fusion.1", 0, 4), ("fusion.2", 4, 8), ("fusion.3", 12, 2),
               ("fusion.4", 14, 6), ("fusion.5", 20, 1), ("copy.6", 21, 3),
               ("fusion.77", 24, 2)]
        return {"modules": [["jit__step(3)", 0.0, 40e-6]],
                "ops": [[name, "bf16[8]", s * 1e-6, d * scale * 1e-6]
                        for name, s, d in ops]}
    raw = {"window": [0.0, 40e-6],
           "planes": {"/device:TPU:0": plane(1.0),
                      "/device:TPU:1": plane(0.5)}}
    joined = op_scopes.join(raw, maps)
    facts = {"window_s": 0.05, "steps": 5}

    def ms(**params):
        seconds = op_scopes.seconds(joined, {"program": "train.step",
                                             **params})
        return seconds / 40e-6 * 1e3 * facts["window_s"] / facts["steps"]
    # averaged over the two devices: 0.75 of the first one's seconds
    assert ms(scopes=["mlp"]) == pytest.approx(0.75 * 12 / 40 * 10)
    assert ms(scopes=["mlp"], **{"pass": "bwd"}) == \
        pytest.approx(0.75 * 8 / 40 * 10)
    assert ms(scopes=["lm_head", "loss_ce"]) == \
        pytest.approx(0.75 * 2 / 40 * 10)
    assert ms(scopes=["optimizer"]) == pytest.approx(0.75 * 6 / 40 * 10)
    # grads alone is no scope; copy.6 has none; fusion.77 is in no map
    assert ms(unscoped=True, ignore=["grads", "grad_accum"]) == \
        pytest.approx(0.75 * (1 + 3 + 2) / 40 * 10)
    assert ms(scopes=["ln_2"]) == 0.0       # mixed is told, not counted


def test_a_trace_without_shapes_or_modules_is_matched_by_name():
    maps = {"train.step": {"module": "jit__step", "ops": {
        "fusion.1": _entry("mlp", "bf16[8]")}}}
    raw = {"window": [0.0, 1.0], "planes": {"/device:TPU:0": {
        "modules": [], "ops": [["fusion.1", "", 0.1, 0.2],
                               ["fusion.2", "", 0.4, 0.1]]}}}
    events = op_scopes.join(raw, maps)["events"]["/device:TPU:0"]
    assert [(e[0], e[4] and e[4]["scope"]) for e in events] == [
        ("train.step", "mlp"), ("train.step", None)]


@pytest.mark.parametrize("registered,wanted,same", [
    ("llm.decode.8", "llm.decode", True), ("llm.decode", "llm.decode", True),
    ("llm.prefill.scatter.64", "llm.prefill", False),
    ("llm.prefill.64", "llm.prefill", True),
    ("train.step", "train.step", True), ("", "train.step", False)])
def test_a_program_is_named_with_or_without_its_bucket(registered, wanted,
                                                       same):
    assert op_scopes.is_program(registered, wanted) is same


# --------------------------------------------------------------- the manifest
def test_new_metrics_are_in_the_manifest_by_name_with_their_scopes():
    bench = manifest.load_manifest()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    for name, scopes in NEW_METRICS.items():
        entry = by_name[name]
        assert entry["unit"] == "ms" and entry["better"] == "lower"
        assert entry["source"] == "device_trace"
        assert entry["workloads"], name
        spec = manifest.metric_spec("per_layer", name)
        assert spec["layer"] == entry["layer"]
        assert spec["moves"] == entry["moves"]
        train = spec["reducer"] == "scope_ms_per_step"
        assert train or spec["reducer"] == "scope_ms_in_program_span"
        # whichever cells list it, now or later, are cells of its job
        kinds = {manifest.load_cell(bench, w)["traffic_file"]["kind"]
                 for w in entry["workloads"]}
        assert kinds == {"train" if train else "serve"}
        assert entry["moves"] == ("train_tokens_per_s_per_chip" if train
                                  else "serve_itl_p50_ms")
        assert spec["params"]["program"] == ("train.step" if train
                                             else "llm.decode")
        if scopes is None:
            assert spec["params"]["unscoped"] is True
            assert "grads" in spec["what"]
        else:
            assert spec["params"]["scopes"] == scopes
            for scope in scopes:            # `what` names what it reads
                assert scope in spec["what"], (name, scope)
    for name in ("train_program.optimizer_ms", "train_program.head_loss_ms",
                 "train_program.attn_scope_ms", "train_program.unscoped_ms"):
        assert set(TRAIN) <= set(by_name[name]["workloads"])
    assert set(TRAIN[:1]) <= set(by_name["train_program.mlp_ms"]["workloads"])
    for name in ("moe.dispatch_ms", "moe.experts_scope_ms"):
        assert set(TRAIN[1:]) <= set(by_name[name]["workloads"])
        assert TRAIN[0] not in by_name[name]["workloads"]   # XL routes nothing
    for name in ("model_step.decode_mlp_ms", "model_step.decode_head_ms",
                 "model_step.decode_attn_ms"):
        assert set(SERVE) <= set(by_name[name]["workloads"])
    assert set(SERVE[1:]) <= set(by_name["ssm.decode_mixer_ms"]["workloads"])
