"""Mesh/sharding/SPMD-program tests on the 8-virtual-device CPU rig
(SURVEY.md §4 testing blueprint item b)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel import spmd
from ray_tpu.parallel.mesh import MeshConfig


def test_mesh_config_resolution():
    cfg = MeshConfig(data=-1, tensor=2).resolved(8)
    assert cfg.data == 4 and cfg.tensor == 2 and cfg.num_devices == 8
    with pytest.raises(ValueError):
        MeshConfig(data=3, tensor=2).resolved(8)


def test_build_mesh_axes():
    mesh = mesh_lib.build_mesh(MeshConfig(data=2, tensor=2, context=2))
    assert mesh.shape["data"] == 2
    assert mesh.shape["tensor"] == 2
    assert mesh.shape["context"] == 2
    assert mesh.size == 8


def test_param_specs_stacked_blocks():
    cfg = gpt2.tiny()
    params = jax.eval_shape(lambda: gpt2.init_params(jax.random.key(0), cfg))
    specs = mesh_lib.param_specs(params)
    assert specs["wte"] == P("tensor", "fsdp")
    assert specs["blocks"]["attn_qkv"]["kernel"] == \
        P("pipeline", "fsdp", None, "tensor")
    assert specs["blocks"]["mlp_out"]["kernel"] == \
        P("pipeline", "tensor", "fsdp")
    # rank trimming: ln_f scale is rank-1 → replicated
    assert specs["ln_f"]["scale"] == P(None)


def test_gpt2_forward_shapes_and_loss():
    cfg = gpt2.tiny()
    params = gpt2.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = gpt2.forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    loss = gpt2.loss_fn(params, batch, cfg)
    # uniform-ish init → loss near log(vocab)
    assert 0 < float(loss) < 2 * np.log(cfg.vocab_size)


def test_seq_activation_rules_filled():
    """The sharding-rules table has no ``"seq": None`` hole:
    sequence-parallel regions shard tokens over the seq axis
    composed with the tensor group (Megatron-SP), and the helper builds
    the canonical residual-stream spec from logical names."""
    assert mesh_lib.ACTIVATION_RULES["seq"] == ("seq", "tensor")
    assert mesh_lib.ACTIVATION_RULES["seq_attn"] == "context"
    spec = mesh_lib.activation_spec("batch", "seq", "embed")
    assert spec == P(("data", "fsdp"), ("seq", "tensor"), None)
    with pytest.raises(KeyError):
        mesh_lib.activation_spec("batch", "nonsense")


def test_seq_mesh_roundtrips_through_train_step():
    """2D (data, seq) mesh: the train step runs, state round-trips its
    shardings (every output leaf keeps the declared sharding so step N+1
    consumes step N's output without resharding), and the sequence-
    parallel program trains."""
    mc = MeshConfig(data=2, seq=4)
    mesh = mesh_lib.build_mesh(mc.resolved(8))
    assert mesh.shape["seq"] == 4 and mesh.shape["data"] == 2
    cfg = gpt2.tiny()
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        optimizer=spmd.default_optimizer(lr=1e-2, warmup=1, total_steps=50),
        mesh=mesh, mesh_config=mc)
    state = prog.init_fn(jax.random.key(0))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 33)).astype(np.int32)
    batch = spmd.shard_batch(prog, {"tokens": toks})
    first = None
    for _ in range(5):
        state, m = prog.step_fn(state, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first
    # sharding round-trip: output state leaves carry the declared
    # shardings (donation + re-feed would silently reshard otherwise)
    declared = jax.tree_util.tree_leaves(
        prog.state_shardings,
        is_leaf=lambda x: hasattr(x, "spec"))
    actual = jax.tree_util.tree_leaves(state)
    assert len(declared) == len(actual)
    for sh, leaf in zip(declared, actual):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), \
            (sh, leaf.sharding)


@pytest.mark.parametrize("mc", [
    MeshConfig(data=8),
    MeshConfig(data=2, tensor=4),
    MeshConfig(data=2, fsdp=2, tensor=2),
])
def test_train_program_runs_and_loss_decreases(mc):
    cfg = gpt2.tiny()
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        optimizer=spmd.default_optimizer(lr=1e-2, warmup=1, total_steps=50),
        mesh_config=mc)
    state = prog.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (8, 33)).astype(np.int32)
    batch = spmd.shard_batch(prog, {"tokens": tokens})
    first = None
    for _ in range(10):
        state, metrics = prog.step_fn(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first  # overfits one batch
    assert int(jax.device_get(state.step)) == 10


def test_adamw_compact_matches_f32_adamw():
    """bf16-moment AdamW tracks optax's f32 AdamW on a real objective —
    the storage dtype must not change the trajectory materially."""
    import optax
    from ray_tpu.parallel import optim

    def loss(p):
        return jnp.sum((p["w"] @ p["w"].T - jnp.eye(8)) ** 2) + \
            jnp.sum(p["b"] ** 2)

    p0 = {"w": jax.random.normal(jax.random.key(0), (8, 8)) * 0.5,
          "b": jnp.ones((8,))}
    ref_opt = optax.chain(optax.clip_by_global_norm(1.0),
                          optax.adamw(1e-2, weight_decay=0.01))
    cpt_opt = optim.adamw_compact(1e-2, weight_decay=0.01, clip=1.0)

    def run(opt):
        p, s = p0, opt.init(p0)
        for _ in range(60):
            g = jax.grad(loss)(p)
            u, s = opt.update(g, s, p)
            p = optim.apply_updates_mixed(p, u)
        return p, s

    pr, _ = run(ref_opt)
    pc, sc = run(cpt_opt)
    # moments actually stored compactly
    adam_state = next(s for s in jax.tree_util.tree_leaves(
        sc, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x := s, "mu"))
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(adam_state.mu))
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(adam_state.nu))
    np.testing.assert_allclose(float(loss(pr)), float(loss(pc)), rtol=0.05)
    for a, b in zip(jax.tree_util.tree_leaves(pr),
                    jax.tree_util.tree_leaves(pc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-2)


def test_grad_accumulation_matches_single_step():
    """accum_steps=4 over one global batch == one full-batch step (mean of
    microbatch-mean grads is the full-batch mean), modulo bf16 noise."""
    cfg = gpt2.tiny()
    toks = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (8, 33)).astype(np.int32)
    states = {}
    for name, acc in [("full", 1), ("accum", 4)]:
        prog = spmd.build_train_program(
            loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
            init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
            optimizer=spmd.default_optimizer(lr=1e-2, warmup=1,
                                             total_steps=50),
            mesh_config=MeshConfig(data=2, tensor=4), accum_steps=acc)
        state = prog.init_fn(jax.random.key(5))
        state, m = prog.step_fn(state, spmd.shard_batch(prog,
                                                        {"tokens": toks}))
        states[name] = (state, float(m["loss"]), float(m["grad_norm"]))
    assert states["full"][1] == pytest.approx(states["accum"][1], rel=2e-2)
    assert states["full"][2] == pytest.approx(states["accum"][2], rel=5e-2)
    for a, b in zip(jax.tree_util.tree_leaves(states["full"][0].params),
                    jax.tree_util.tree_leaves(states["accum"][0].params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-2, rtol=2e-1)


def test_accum_bf16_state_loss_decreases_on_mesh():
    """The XL single-chip recipe — bf16 params + bf16 moments + microbatch
    accumulation — trains (loss decreases) on the 8-device virtual mesh."""
    import dataclasses
    cfg = dataclasses.replace(gpt2.tiny(), param_dtype=jnp.bfloat16)
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        optimizer=spmd.default_optimizer(lr=1e-2, warmup=1, total_steps=50,
                                         moments_dtype=jnp.bfloat16),
        mesh_config=MeshConfig(data=4, tensor=2), accum_steps=2)
    state = prog.init_fn(jax.random.key(0))
    moment_leaves = [l for l in jax.tree_util.tree_leaves(state.opt_state)
                     if getattr(l, "ndim", 0) > 0]
    assert moment_leaves and all(l.dtype == jnp.bfloat16
                                 for l in moment_leaves)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 33)).astype(np.int32)
    batch = spmd.shard_batch(prog, {"tokens": toks})
    first = None
    for _ in range(10):
        state, m = prog.step_fn(state, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first


def test_tensor_parallel_matches_dp_numerics():
    """Same init, same batch → same loss whether TP or pure DP (GSPMD
    correctness check for the sharding rules)."""
    cfg = gpt2.tiny()
    losses = {}
    for name, mc in [("dp", MeshConfig(data=8)),
                     ("tp", MeshConfig(data=1, tensor=8))]:
        prog = spmd.build_train_program(
            loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
            init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
            mesh_config=mc)
        state = prog.init_fn(jax.random.key(7))
        toks = np.arange(8 * 17, dtype=np.int32).reshape(8, 17) % cfg.vocab_size
        _, m = prog.step_fn(state, spmd.shard_batch(prog, {"tokens": toks}))
        losses[name] = float(m["loss"])
    assert losses["dp"] == pytest.approx(losses["tp"], rel=2e-3)


def test_odd_vocab_trains_on_a_tensor_mesh():
    """GPT-2's 50,257-row embedding has no even split: a rule's mesh axis
    that does not divide a leaf's dim is dropped for that leaf (jit
    refuses an uneven sharding), and the step still runs."""
    cfg = gpt2.GPT2Config(vocab_size=509, n_positions=32, n_embd=64,
                          n_layer=2, n_head=4)
    mc = MeshConfig(data=1, fsdp=2, tensor=2).resolved(4)
    mesh = mesh_lib.build_mesh(mc, jax.devices()[:4])
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        mesh=mesh, mesh_config=mc)
    assert prog.state_shardings.params["wte"].spec == P(None, "fsdp")
    assert prog.state_shardings.params["blocks"]["mlp_in"]["kernel"].spec \
        == P("pipeline", "fsdp", "tensor")
    state = prog.init_fn(jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, 509, (4, 33)).astype(np.int32)
    b = spmd.shard_batch(prog, {"inputs": toks[:, :-1],
                                "targets": toks[:, 1:]})
    _, m = prog.step_fn(state, b)
    assert np.isfinite(float(m["loss"]))
    assert "wte" in prog.jitted_step.lower(state, b).as_text()


def test_under_gspmd_is_false_inside_shard_map():
    """Kernels ask ``under_gspmd()`` before they are placed: true only on
    a mesh of several devices outside every shard_map region."""
    seen = {}

    def body(x):
        seen["inside"] = mesh_lib.under_gspmd()
        return x

    assert not mesh_lib.under_gspmd()                   # no ambient mesh
    with mesh_lib.ambient_mesh(mesh_lib.single_device_mesh()):
        assert not mesh_lib.under_gspmd()               # one device
    mesh = mesh_lib.build_mesh(MeshConfig(data=4), jax.devices()[:4])
    with mesh_lib.ambient_mesh(mesh):
        assert mesh_lib.under_gspmd()
        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data")))(jnp.zeros((4, 2)))
    assert seen == {"inside": False}
