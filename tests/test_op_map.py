"""``tracing.op_map``: what each instruction of a compiled step program is,
read from the module's own stack-frame tables, on the CPU and with
``jax_include_full_tracebacks_in_locations`` off as ``ray_tpu/__init__.py``
sets it; and the registry that keeps step programs for ``op_maps``
without lowering, compiling or parsing anything until someone asks."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import deepseek_v3, gpt2, llama
from ray_tpu.parallel import spmd
from ray_tpu.util import tracing

S = jax.ShapeDtypeStruct


@pytest.fixture(autouse=True)
def flag_off_and_clean_registry(monkeypatch):
    """The flag as ``apply_xla_cache_env`` sets it wherever a compile
    cache is in use (tests/conftest.py puts it back), and no program left
    over from another test."""
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    monkeypatch.setattr(tracing, "_PROGRAMS", {})


def _program(mod, cfg):
    """On one device, whatever the test process has (conftest: eight)."""
    from ray_tpu.parallel import mesh as mesh_lib
    mc = mesh_lib.MeshConfig().resolved(1)
    return spmd.build_train_program(
        loss_fn=lambda p, b: mod.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: mod.init_params(rng, cfg),
        mesh=mesh_lib.build_mesh(mc, jax.devices()[:1]), mesh_config=mc)


def _shapes(prog, seq, batch=2):
    state = jax.eval_shape(prog.jitted_init, jax.random.key(0))
    tokens = S((batch, seq), jnp.int32)
    return state, {"inputs": tokens, "targets": tokens}


def _step_map(mod, cfg, seq):
    prog = _program(mod, cfg)
    return tracing.op_map(prog.jitted_step.lower(*_shapes(prog, seq))
                          .compile())


def _having(ops, component, **keys):
    return [e for e in ops.values()
            if component in e["scope"].split("/")
            and all(e[k] == v for k, v in keys.items())]


# ------------------------------------------------------------ the train step
@pytest.fixture(scope="module")
def gpt2_map():
    # a module's fixture is set up before conftest's per-test one reads the
    # flag it puts back: left off here, it stayed off for every later file
    # of the worker (tests/test_named_scopes.py then read other locations)
    full = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        return _step_map(gpt2, gpt2.tiny(), 64)
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", full)


@pytest.mark.parametrize("scope", ["mlp", "attn", "ln_1", "ln_2", "attn_qkv",
                                   "attn_out", "embed", "lm_head", "loss_ce",
                                   "ln_f"])
def test_the_train_step_names_every_layer_kind_forward_and_backward(
        gpt2_map, scope):
    assert _having(gpt2_map, scope, **{"pass": "fwd"}), scope
    assert _having(gpt2_map, scope, **{"pass": "bwd"}), scope


def test_two_norms_of_one_source_line_are_told_apart(gpt2_map):
    """``ln_1`` and ``ln_2`` both call ``gpt2._layer_norm``: one line of
    source, two scopes."""
    def lines(scope):
        return {e["src"] for e in _having(gpt2_map, scope, prim="rsqrt")}
    assert lines("ln_1") and lines("ln_1") == lines("ln_2")
    assert all(src.startswith("gpt2.py:") for src in lines("ln_1"))


def test_optimizer_and_grads_are_neither_forward_nor_backward(gpt2_map):
    optimizer = _having(gpt2_map, "optimizer")
    assert optimizer and {e["pass"] for e in optimizer} == {""}
    assert _having(gpt2_map, "grads")
    # nothing of the optimizer is under grads, nor the other way round
    assert not [e for e in optimizer if "grads" in e["scope"].split("/")]


def test_the_layer_scans_bodies_are_mapped_with_their_pass(gpt2_map):
    """Inside a loop body the name stack is relative (``mlp``, and
    ``checkpoint/mlp`` in the backward), so the pass is the body's."""
    fwd = _having(gpt2_map, "mlp", path="mlp", prim="dot_general")
    assert fwd and {e["pass"] for e in fwd} == {"fwd"}
    bwd = _having(gpt2_map, "mlp", path="checkpoint/mlp", prim="dot_general")
    assert bwd and {e["pass"] for e in bwd} == {"bwd"}
    recomputed = _having(gpt2_map, "mlp",
                         path="checkpoint/rematted_computation/mlp")
    assert recomputed and {e["pass"] for e in recomputed} == {"bwd"}


def test_a_fusion_that_spans_two_scopes_says_so(gpt2_map):
    mixed = [e for e in gpt2_map.values() if "mixed" in e]
    assert mixed
    for entry in mixed:
        assert len(entry["mixed"]) > 1
        assert entry["mixed"] == sorted(set(entry["mixed"]))
    # its own scope (its root's) is one of those it names
    assert any(e["scope"] in e["mixed"] for e in mixed)


def test_every_entry_has_the_contracts_keys(gpt2_map):
    for entry in gpt2_map.values():
        assert {"scope", "path", "pass", "prim", "src", "shape"} \
            <= set(entry), entry
        assert entry["pass"] in ("fwd", "bwd", "")
        assert "jvp(" not in entry["scope"] and "jit(" not in entry["scope"]


def test_the_mixture_names_dispatch_experts_and_combine():
    ops = _step_map(llama, llama.PRESETS["tiny-moe"](), 32)
    for scope in ("moe_dispatch", "moe_experts", "moe_combine", "router"):
        assert _having(ops, scope, **{"pass": "fwd"}), scope
        assert _having(ops, scope, **{"pass": "bwd"}), scope
    for scope in ("ln_1", "attn_qkv", "rope", "attn", "attn_out", "ln_2",
                  "embed", "lm_head", "ln_f"):
        assert _having(ops, scope), scope
    assert not _having(ops, "mlp")          # the experts are the FFN


def test_the_latent_attention_model_names_its_dense_and_sparse_parts():
    ops = _step_map(deepseek_v3, deepseek_v3.tiny(seq=64), 64)
    for scope in ("mla", "latent", "attn", "attn_qkv", "attn_out", "mlp",
                  "moe", "shared", "moe_dispatch", "moe_experts",
                  "moe_combine", "ln_1", "ln_2", "embed", "lm_head"):
        assert _having(ops, scope), scope


@pytest.mark.parametrize("path,scope", [
    ("jit(_step)/grads/transpose(jvp(moe))/moe_dispatch",
     "grads/moe/moe_dispatch"),
    ("jit(_step)/grads/jvp(embed)/cast_weights", "grads/embed/cast_weights"),
    ("checkpoint/rematted_computation/ln_1/jit(_var)", "ln_1"),
    ("attn_qkv/bte,eck->btck", "attn_qkv"),
    ("jit(_step)/grads/jvp()", "grads"),
    ("while/body/closed_call", ""),
    ("jit(_step)/optimizer/jit(clip)", "optimizer"),
    ("", ""),
])
def test_scope_is_the_path_without_what_transforms_wrote(path, scope):
    assert tracing.scope_of(path) == scope


# ------------------------------------------------- the serving step programs
def _engine_cfg(model):
    from ray_tpu.serve.llm import EngineConfig
    return EngineConfig(model=model, num_blocks=64, block_size=8,
                        max_num_seqs=4, max_model_len=64,
                        max_prefill_tokens=32, prefill_len_buckets=(16, 64),
                        decode_batch_buckets=(4,), share_weights=False)


@pytest.mark.parametrize("model", ["gpt2:tiny", "llama:tiny"])
def test_the_runners_programs_register_and_map_head_and_paged_attention(
        model):
    from ray_tpu.serve import llm
    eng = llm.LLMEngine(_engine_cfg(model), start=False)
    try:
        assert tracing.registered_programs() == []
        eng.runner.prefill([1, 2, 3, 4, 5])
        eng.runner.decode(np.zeros(2, np.int32), np.zeros(2, np.int32),
                          eng.cache.pool, np.zeros((2, 8), np.int32),
                          np.ones(2, np.int32))
        assert tracing.registered_programs() == [
            "llm.decode.4", "llm.prefill.16", "llm.prefill.scatter.16"]
        maps = tracing.op_maps()
    finally:
        eng.shutdown()
    decode = maps["llm.decode.4"]
    assert decode["module"].startswith("jit_")
    for scope in ("lm_head", "paged_attention", "mlp", "attn_qkv",
                  "attn_out", "embed", "kv_write"):
        assert _having(decode["ops"], scope), scope
    # the runner's greedy argmax is under the head's scope
    assert [e for e in _having(decode["ops"], "lm_head")
            if e["src"].startswith("model_runner.py:")]
    assert {e["pass"] for e in decode["ops"].values()} == {""}
    assert _having(maps["llm.prefill.16"]["ops"], "attn")
    assert _having(maps["llm.prefill.scatter.16"]["ops"], "kv_write")


def test_a_new_program_of_a_name_replaces_the_old():
    tracing.register_program("train.step", "first", ())
    tracing.register_program("train.step", "second", ())
    assert tracing.registered_programs() == ["train.step"]
    assert tracing._PROGRAMS["train.step"][0] == "second"


# ----------------------------------- registering changes and costs nothing
# sha256 (first 16 digits) of each train step's StableHLO as the parent
# of the PR that added the op map lowered it (commit f004de3, this jax).
# The scopes that PR added to llama.py and the in-place lowering of
# ``jax.checkpoint`` (models/_common.py) change locations only, which
# ``as_text()`` does not print.  The serving programs' digests are pinned
# in tests/test_falcon_h1.py.  ``llama:tiny-moe`` is the step's since PR 45
# changed it on purpose (ops/moe.dropless_experts: the router's weight on
# the hidden rows, the combine the dispatch transposed) and PR 49 did again
# (the same function: everything read off one sort, gathers that promise
# their indices, the k slots leading, the counts a compare and a sum) and
# PR 68 a third time (ops/moe.choose_experts: the router's ``top_k`` stands
# inside a ``custom_vjp`` whose backward is a select over (N, E); what left
# is the ``scatter`` of ``top_k``'s own derivative).
PARENT_TRAIN_STEPS = {
    "gpt2:tiny": (gpt2, gpt2.tiny, 64, "f76d5da583f18826"),
    "llama:tiny": (llama, llama.PRESETS["tiny"], 32, "4d2cd3d1fa54a1c8"),
    "llama:tiny-moe": (llama, llama.PRESETS["tiny-moe"], 32,
                       "72148e509ba80f38"),
}


@pytest.mark.parametrize("model", sorted(PARENT_TRAIN_STEPS))
def test_train_steps_lower_byte_for_byte_as_on_the_parent(model):
    mod, cfg, seq, digest = PARENT_TRAIN_STEPS[model]
    prog = _program(mod, cfg())
    text = prog.jitted_step.lower(*_shapes(prog, seq)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


class _Count:
    """How often jax lowered or compiled anything, by its own monitoring
    events."""
    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, _seconds, **_):
        name = self.EVENTS.get(event)
        if name:
            setattr(self, name, getattr(self, name) + 1)

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self)


def test_a_trainer_never_lowers_compiles_or_parses_for_the_map(monkeypatch):
    """Steps register the program at the first call and do nothing else:
    jax lowers and compiles once for the step itself however many steps
    run, ``op_map`` is not called, and asking is what costs."""
    parsed = []
    real = tracing.op_map
    monkeypatch.setattr(tracing, "op_map",
                        lambda c: parsed.append(1) or real(c))
    prog = _program(gpt2, gpt2.tiny())
    state = prog.init_fn(jax.random.key(0))
    tokens = np.zeros((2, 64), np.int32)
    batch = {"inputs": tokens, "targets": tokens}
    assert prog.abstract_args is None
    with pytest.raises(RuntimeError, match="not been called"):
        prog.op_map()
    state, _ = prog.step_fn(state, batch)       # compiles the step
    count = _Count()
    try:
        for _ in range(3):
            state, metrics = prog.step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        assert (count.lowered, count.compiled, parsed) == (0, 0, [])
        assert tracing.registered_programs() == ["train.step"]
        jitted, args = tracing._PROGRAMS["train.step"]
        assert jitted is prog.jitted_step and args is prog.abstract_args
        ops = prog.op_map()                     # asking is what parses
        assert parsed == [1]
    finally:
        count.close()
    assert _having(ops, "optimizer") and _having(ops, "mlp")
    assert tracing.op_maps()["train.step"]["module"] == "jit__step"
