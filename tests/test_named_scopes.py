"""Every layer kind and kernel of the main path has a name (ISSUE 25):
``jax.named_scope`` on the model's layers and the train step's phases,
``name=`` on the Pallas kernels.  Metadata only: the names are in
the lowered program's locations and in the jaxpr, where profilers and
HLO dumps find them (what reaches the v5e trace: PERF.md, section 7).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel import spmd
from ray_tpu.parallel.mesh import MeshConfig

CFG = gpt2.tiny()
LAYERS = ["embed", "ln_1", "attn_qkv", "attn", "attn_out", "ln_2", "mlp",
          "ln_f", "lm_head", "cast_weights"]
DECODE = LAYERS + ["kv_layout", "paged_gather", "paged_attention"]
TRAIN = LAYERS + ["loss_ce", "grads", "optimizer"]


def _scopes(lowered) -> set:
    """The components of every location name of the lowered module."""
    text = lowered.as_text(debug_info=True)
    parts = set()
    for name in re.findall(r'loc\("([^"]*)"', text):
        for part in name.split("/"):
            while part.endswith(")") and "(" in part:   # jvp(attn) -> attn
                part = part[part.index("(") + 1:-1]
            parts.add(part)
    return parts


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def decode_scopes(params):
    from ray_tpu.serve.llm.kv_cache import device_shape
    pool = np.zeros(device_shape(4, CFG.n_layer, 8, CFG.n_head,
                                 CFG.head_dim), np.float32)
    fn = jax.jit(lambda *a: gpt2.forward_decode(*a, cfg=CFG))
    return _scopes(fn.lower(
        params, np.zeros(2, np.int32), np.zeros(2, np.int32), pool,
        np.zeros((2, 3), np.int32), np.ones(2, np.int32)))


@pytest.fixture(scope="module")
def prefill_scopes(params):
    fn = jax.jit(lambda p, t, last: gpt2.forward_prefill(p, t, CFG, last))
    return _scopes(fn.lower(params, np.zeros((1, 16), np.int32),
                            jnp.int32(3)))


def _step_scopes(batch_rows: int, **kwargs) -> set:
    """The scopes of a one-device train step's lowered program."""
    mc = MeshConfig(data=1).resolved(1)
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, CFG),
        init_params_fn=lambda rng: gpt2.init_params(rng, CFG),
        mesh=mesh_lib.build_mesh(mc, jax.devices()[:1]), mesh_config=mc,
        **kwargs)
    state = jax.eval_shape(prog.jitted_init, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((batch_rows, 16), jnp.int32)
             for k in ("inputs", "targets")}
    return _scopes(prog.jitted_step.lower(state, batch))


@pytest.fixture(scope="module")
def train_scopes():
    return _step_scopes(2)


@pytest.mark.parametrize("name", DECODE)
def test_decode_step_names_the_scope(decode_scopes, name):
    assert name in decode_scopes


@pytest.mark.parametrize("name", LAYERS)
def test_prefill_names_the_scope(prefill_scopes, name):
    assert name in prefill_scopes


@pytest.mark.parametrize("name", TRAIN)
def test_train_step_names_the_scope(train_scopes, name):
    assert name in train_scopes


@pytest.mark.parametrize("case", ["a_cluster_in_this_process", "the_next_test"])
def test_locations_are_named_alike_after_a_cluster_test(params, case):
    """``ray_tpu.init()`` turns jax's full tracebacks off in its process
    (a compile cache's keys need that), and a forward-only program under
    ``jax.checkpoint`` then loses its scopes; ``conftest.py`` puts the
    option back after every test, so the case that follows reads the
    names this file's other cases read when run alone."""
    import ray_tpu
    if case == "a_cluster_in_this_process":
        ray_tpu.init(num_cpus=1)
        ray_tpu.shutdown()
        if jax.config.jax_compilation_cache_dir:
            assert not jax.config.jax_include_full_tracebacks_in_locations
        return
    assert jax.config.jax_include_full_tracebacks_in_locations
    batch = {"inputs": np.zeros((2, 16), np.int32),
             "targets": np.zeros((2, 16), np.int32)}
    got = _scopes(jax.jit(lambda p, b: gpt2.loss_fn(p, b, CFG))
                  .lower(params, batch))
    assert {"mlp", "attn", "lm_head", "loss_ce", "ln_f"} <= got


def test_grad_accumulation_is_named():
    assert {"grad_accum", "grads", "optimizer"} <= \
        _step_scopes(4, accum_steps=2)


def _flash_loss(q):
    return flash_attention(q, q, q, True, None, True).sum()


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_pallas_kernel_is_named_in_the_jaxpr(kernel):
    jaxpr = str(jax.make_jaxpr(jax.grad(_flash_loss))(
        jnp.ones((1, 128, 2, 64), jnp.float32)))
    assert f"name={kernel}" in jaxpr
    named = re.findall(r"name=flash_(?:fwd|bwd)\b", jaxpr)
    assert jaxpr.count("pallas_call") == len(named) == 2    # none unnamed
