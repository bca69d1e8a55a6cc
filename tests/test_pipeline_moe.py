"""Pipeline (PP) and expert (EP/MoE) parallelism tests on the 8-device CPU
mesh (SURVEY.md §2.4 rows "Pipeline parallelism" / "Expert parallelism")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe as moe_lib
from ray_tpu.parallel import mesh as mesh_lib, pipeline as pp
from ray_tpu.parallel.mesh import MeshConfig


def _mesh(**axes):
    return mesh_lib.build_mesh(MeshConfig(**axes), jax.devices()[:8])


# ---------------------------------------------------------------- pipeline

def _make_layers(rng, n_layers, d):
    w = jax.random.normal(rng, (n_layers, d, d)) * (1.0 / np.sqrt(d))
    return {"w": w}


def _stage_fn(params, x):
    # params: (layers_per_stage, d, d); sequential blocks within the stage
    def body(h, w):
        return jnp.tanh(h @ w), None
    h, _ = jax.lax.scan(body, x, params["w"])
    return h


def _sequential(params, x):
    def body(h, w):
        return jnp.tanh(h @ w), None
    h, _ = jax.lax.scan(body, x, params["w"])
    return h


def test_pipeline_matches_sequential():
    mesh = _mesh(data=2, pipeline=4)
    d, B, L, S = 16, 8, 8, 4
    params = _make_layers(jax.random.key(0), L, d)
    x = jax.random.normal(jax.random.key(1), (B, d))

    expect = _sequential(params, x)
    staged = pp.stack_stages(params, S)
    x_micro = pp.split_microbatches(x, 4)

    @jax.jit
    def run(p, xm):
        return pp.pipeline_apply(_stage_fn, p, xm, mesh=mesh)

    got = pp.merge_microbatches(run(staged, x_micro))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_single_stage_path():
    mesh = _mesh(data=8, pipeline=1)
    d, B, L = 8, 8, 4
    params = _make_layers(jax.random.key(0), L, d)
    x = jax.random.normal(jax.random.key(1), (B, d))
    staged = pp.stack_stages(params, 1)
    got = pp.merge_microbatches(
        pp.pipeline_apply(_stage_fn, staged, pp.split_microbatches(x, 2),
                          mesh=mesh))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_sequential(params, x)),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_grads_match_sequential():
    mesh = _mesh(pipeline=4, data=2)
    d, B, L, S = 8, 8, 4, 4
    params = _make_layers(jax.random.key(2), L, d)
    x = jax.random.normal(jax.random.key(3), (B, d))

    def loss_seq(p):
        return jnp.sum(_sequential(p, x) ** 2)

    def loss_pp(p_staged):
        y = pp.pipeline_apply(_stage_fn, p_staged, pp.split_microbatches(x, S),
                              mesh=mesh)
        return jnp.sum(pp.merge_microbatches(y) ** 2)

    g_seq = jax.grad(loss_seq)(params)["w"]
    g_pp = pp.unstack_stages(jax.jit(jax.grad(loss_pp))(
        pp.stack_stages(params, S)))["w"]
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                               rtol=1e-4, atol=1e-4)


def test_stack_roundtrip_and_microbatch_pick():
    params = _make_layers(jax.random.key(0), 12, 4)
    rt = pp.unstack_stages(pp.stack_stages(params, 4))
    np.testing.assert_array_equal(np.asarray(rt["w"]),
                                  np.asarray(params["w"]))
    assert pp.pick_num_microbatches(64, 4) == 16
    assert pp.pick_num_microbatches(8, 4) == 8
    with pytest.raises(ValueError):
        pp.stack_stages(params, 5)


# ---------------------------------------------------------------- MoE / EP

def test_moe_matches_dense_reference():
    """With generous capacity (no drops), moe_ffn == per-token gated mixture."""
    B, S, d, ff, E, k = 2, 8, 8, 16, 4, 2
    rng = jax.random.key(0)
    p = moe_lib.init_moe_params(rng, d, ff, E)
    x = jax.random.normal(jax.random.key(1), (B, S, d))

    y, metrics = moe_lib.moe_ffn(x, p["router"], p["w_in"], p["w_out"],
                                 k=k, capacity_factor=8.0)
    assert float(metrics.fraction_dropped) == 0.0

    # reference: every token through its top-k experts, gate-weighted
    tokens = x.reshape(-1, d)
    gates, _, _ = moe_lib.topk_router(tokens, p["router"], k)
    outs = []
    for n in range(tokens.shape[0]):
        acc = jnp.zeros((d,))
        for e in range(E):
            if float(gates[n, e]) > 0:
                h = jax.nn.gelu(tokens[n] @ p["w_in"][e])
                acc = acc + gates[n, e] * (h @ p["w_out"][e])
        outs.append(acc)
    expect = jnp.stack(outs).reshape(B, S, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens():
    B, S, d, ff, E = 1, 16, 4, 8, 2
    p = moe_lib.init_moe_params(jax.random.key(0), d, ff, E)
    # capacity_factor tiny → capacity floor (8) with k=2,N=16,E=2 → some drop
    y, metrics = moe_lib.moe_ffn(
        jax.random.normal(jax.random.key(1), (B, S, d)),
        p["router"], p["w_in"], p["w_out"], k=2, capacity_factor=0.1)
    assert y.shape == (B, S, d)
    assert float(metrics.fraction_dropped) >= 0.0
    assert float(metrics.aux_loss) > 0.0


def test_moe_sharded_matches_unsharded():
    """EP over the expert axis + DP over data produces identical numerics."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh(data=2, expert=4)
    B, S, d, ff, E = 4, 8, 8, 16, 4
    p = moe_lib.init_moe_params(jax.random.key(0), d, ff, E)
    x = jax.random.normal(jax.random.key(1), (B, S, d))

    y_ref, m_ref = moe_lib.moe_ffn(x, p["router"], p["w_in"], p["w_out"],
                                   k=2, capacity_factor=4.0)

    xs = jax.device_put(x, NamedSharding(mesh, P(("data",))))
    ps = {
        "router": jax.device_put(p["router"], NamedSharding(mesh, P())),
        "w_in": jax.device_put(p["w_in"], NamedSharding(mesh, P("expert"))),
        "w_out": jax.device_put(p["w_out"], NamedSharding(mesh, P("expert"))),
    }

    @jax.jit
    def run(ps, xs):
        return moe_lib.moe_ffn(xs, ps["router"], ps["w_in"], ps["w_out"],
                               k=2, capacity_factor=4.0)

    y_sh, m_sh = run(ps, xs)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(m_sh.aux_loss), float(m_ref.aux_loss),
                               rtol=1e-5)


def test_moe_grads_flow():
    B, S, d, ff, E = 2, 4, 8, 16, 4
    p = moe_lib.init_moe_params(jax.random.key(0), d, ff, E)
    x = jax.random.normal(jax.random.key(1), (B, S, d))

    def loss(p):
        y, m = moe_lib.moe_ffn(x, p["router"], p["w_in"], p["w_out"],
                               k=2, capacity_factor=4.0)
        return jnp.mean(y ** 2) + 0.01 * m.aux_loss + 0.001 * m.router_z_loss

    g = jax.grad(loss)(p)
    for name in ("router", "w_in", "w_out"):
        assert np.isfinite(np.asarray(g[name])).all()
        assert float(jnp.abs(g[name]).sum()) > 0.0


# ------------------------------------------------------- GPT-2 PP end-to-end

def test_gpt2_pipeline_forward_matches_scan():
    from ray_tpu.models import gpt2
    mesh = _mesh(data=2, pipeline=4)
    base = gpt2.tiny(vocab=64, seq=16)
    cfg = gpt2.GPT2Config(**{**base.__dict__, "n_layer": 4,
                             "dtype": jnp.float32})
    cfg_pp = gpt2.GPT2Config(**{**cfg.__dict__, "pipeline_axis": "pipeline",
                                "num_microbatches": 4})
    params = gpt2.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)

    ref = gpt2.forward(params, tokens, cfg)
    with mesh_lib.ambient_mesh(mesh):
        got = jax.jit(lambda p, t: gpt2.forward(p, t, cfg_pp))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gpt2_pipeline_train_step():
    """Full fwd+bwd+optimizer over a pp=2,tensor=2,data=2 mesh."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import spmd
    mesh = _mesh(data=2, pipeline=2, tensor=2)
    base = gpt2.tiny(vocab=64, seq=16)
    cfg = gpt2.GPT2Config(**{**base.__dict__, "n_layer": 2,
                             "pipeline_axis": "pipeline",
                             "num_microbatches": 2})
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda r: gpt2.init_params(r, cfg),
        mesh=mesh, mesh_config=MeshConfig(data=2, pipeline=2, tensor=2))
    state = prog.init_fn(jax.random.key(0))
    tokens = np.arange(8 * 17, dtype=np.int32).reshape(8, 17) % 64
    batch = spmd.shard_batch(prog, {"inputs": tokens[:, :-1],
                                    "targets": tokens[:, 1:]})
    state, metrics = prog.step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1
