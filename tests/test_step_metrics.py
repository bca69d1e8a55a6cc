"""What a loss function hands to ``spmd.report_step_metrics`` joins the
train step's metrics; a loss that reports nothing gives the step it
always gave; and the expert layer's scopes name the lowered step."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, llama
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel import spmd
from ray_tpu.parallel.mesh import MeshConfig

MOE = dataclasses.replace(llama.tiny_moe(), dtype=jnp.float32)
MOE_KEYS = {"moe_aux_loss", "moe_z_loss", "moe_load_max_over_mean"}
STEP_KEYS = {"loss", "grad_norm", "step"}


def _program(mod, cfg, **kwargs):
    mc = MeshConfig(data=1).resolved(1)
    return spmd.build_train_program(
        loss_fn=lambda p, b: mod.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: mod.init_params(rng, cfg),
        mesh=mesh_lib.build_mesh(mc, jax.devices()[:1]), mesh_config=mc,
        **kwargs)


def _batch(cfg, rows=4, seq=24, seed=0):
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                                (rows, seq + 1))
    return {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32)}


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_a_scalar_loss_steps_as_it_always_did(accum_steps):
    """GPT-2 reports nothing: three metrics, and no result of the lowered
    step beyond the state's leaves and those three."""
    cfg = gpt2.tiny()
    prog = _program(gpt2, cfg, accum_steps=accum_steps)
    state = jax.eval_shape(prog.jitted_init, jax.random.key(0))
    new, metrics = jax.eval_shape(prog.jitted_step, state, _batch(cfg))
    assert set(metrics) == STEP_KEYS
    text = prog.jitted_step.lower(state, _batch(cfg)).as_text()
    main = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{",
                     text, re.S)
    results = main.group(2).count("tensor<")
    assert results == len(jax.tree_util.tree_leaves(new)) + len(STEP_KEYS)


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_router_statistics_leave_the_step(accum_steps):
    prog = _program(llama, MOE, accum_steps=accum_steps)
    state = prog.init_fn(jax.random.key(1))
    params = jax.tree_util.tree_map(jnp.copy, state.params)
    batch = _batch(MOE)
    _, metrics = prog.step_fn(state, spmd.shard_batch(prog, batch))
    assert set(metrics) == STEP_KEYS | MOE_KEYS
    halves = [batch] if accum_steps == 1 else [
        {k: v[:2] for k, v in batch.items()},
        {k: v[2:] for k, v in batch.items()}]
    want = {k: 0.0 for k in MOE_KEYS | {"loss"}}
    for half in halves:
        _, stats = llama.forward_hidden(params, half["inputs"], MOE)
        want["moe_aux_loss"] += float(stats.balance_loss.mean())
        want["moe_z_loss"] += float(stats.z_loss.mean())
        want["moe_load_max_over_mean"] += float(stats.load_max_over_mean.max())
        want["loss"] += float(llama.loss_fn(params, half, MOE))
    for key, total in want.items():
        assert float(metrics[key]) == pytest.approx(total / len(halves),
                                                    rel=1e-5), key
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0


def test_reporting_outside_a_step_is_a_no_op():
    """The loss function stays a scalar function wherever it is called."""
    params = llama.init_params(jax.random.key(2), MOE)
    loss = jax.jit(lambda p, b: llama.loss_fn(p, b, MOE))(params, _batch(MOE))
    assert loss.shape == () and np.isfinite(float(loss))
    spmd.report_step_metrics(anything=jnp.zeros(()))    # nobody listens


def test_the_expert_layers_scopes_name_the_step():
    prog = _program(llama, MOE)
    state = jax.eval_shape(prog.jitted_init, jax.random.key(0))
    text = prog.jitted_step.lower(state, _batch(MOE)).as_text(debug_info=True)
    names = "\n".join(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("qk_norm", "rope", "router", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn", "lm_head", "loss_ce", "grads",
                  "optimizer"):
        assert re.search(rf"(^|[/(]){scope}([/)]|$)", names, re.M), scope
    # dropless: the step holds a ragged dot and no one-hot dispatch tensor
    assert "ragged_dot" in text
    n, e = 4 * 24, MOE.n_experts
    assert not re.search(rf"tensor<{n}x{e}x\d+x", text)
