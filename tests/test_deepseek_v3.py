"""models/deepseek_v3.py (latent attention, a dense prefix, held routed
experts beside a shared one behind a sigmoid router) against its plain
float32 reference at a tiny size on the CPU, and the contracts of what it
forced: an expert layer told which experts it holds (the share test), the
selection bias, dropless among the held, the flash kernel at two widths,
the grouped matmul's tile, the optimizer's buffers, the mesh rules."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import kanana_ref
from ray_tpu.models import deepseek_v3 as ds
from ray_tpu.ops import moe

# hidden 64; 1 dense + 2 sparse layers; 4 heads of 16 + 8 / 12; latent 16;
# 8 routed experts of width 24, 4 held, 2 a token; 1 shared
CFG = dataclasses.replace(ds.tiny(vocab=211, seq=48), dtype=jnp.float32,
                          remat=False)
# both sides compute in float32, so they differ by the order of sums only
ATOL = 2e-4


def settings(cfg):
    first = cfg.first_held_expert
    return {"num_attention_heads": cfg.n_head,
            "qk_nope_head_dim": cfg.qk_nope_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_head_dim,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scale,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "held_expert_ids": list(range(first,
                                          first + cfg.n_held_experts))}


def random_tree(cfg, seed=3):
    """Matrices five times the init (so that routing is decisive and the
    logits are O(1)), norm scales away from 1, a bias large enough to
    change choices."""
    rng = np.random.default_rng(seed + 2)

    def leaf(path, a):
        key = jax.tree_util.keystr(path)
        if "scale" in key:
            return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        if "select_bias" in key:
            return jnp.asarray(rng.normal(size=a.shape) * 0.1, a.dtype)
        return a * 5
    return jax.tree_util.tree_map_with_path(
        leaf, jax.jit(ds.init_params, static_argnums=1)(jax.random.key(seed),
                                                        cfg))


@pytest.fixture(scope="module")
def params():
    return random_tree(CFG)


@pytest.fixture(scope="module")
def batch():
    toks = np.random.default_rng(1).integers(0, CFG.vocab_size, (2, 33))
    return {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32)}


def _flat(tree):
    return {jax.tree_util.keystr(p): np.asarray(a)
            for p, a in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def grads(params, batch):
    got = jax.grad(ds.loss_fn)(params, batch, CFG)
    want = jax.grad(kanana_ref.loss)(params, batch["inputs"],
                                     batch["targets"], settings(CFG))
    return _flat(got), _flat(want)


def test_logits_and_loss_equal_the_references(params, batch):
    want = np.asarray(kanana_ref.logits(params, batch["inputs"],
                                        settings(CFG)))
    got = np.asarray(ds.forward(params, batch["inputs"], CFG))
    assert np.abs(got - want).max() < ATOL
    assert np.abs(want).max() > 0.5             # not all-zero logits
    loss = float(ds.loss_fn(params, batch, CFG))
    assert loss == pytest.approx(float(kanana_ref.loss(
        params, batch["inputs"], batch["targets"], settings(CFG))), abs=1e-5)
    assert abs(loss - np.log(CFG.vocab_size)) < 1.0


GROUPS = ["wte", "lm_head", "norm_f", "attn_norm", "wq", "wkv_a", "kv_norm",
          "wkv_b", "wo", "mlp_norm", "router", "experts", "shared",
          "dense_blocks']['w_"]


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_equal_the_references(grads, group):
    """Every leaf of the group to 1e-4 of the leaf's largest gradient; the
    selection bias has none on either side."""
    got, want = grads
    keys = [k for k in want if f"'{group}" in k]
    assert keys, (group, sorted(want))
    for key in keys:
        scale = np.abs(want[key]).max()
        if "select_bias" in key:
            assert scale == 0 and np.abs(got[key]).max() == 0
            continue
        assert scale > 0, key
        assert np.abs(got[key] - want[key]).max() < 1e-4 * scale + 1e-7, key


def test_every_parameter_group_is_compared(grads):
    got, want = grads
    assert set(got) == set(want)
    for key in want:
        assert any(f"'{g}" in key for g in GROUPS), key


@pytest.mark.parametrize("variant", [{"rope": "half"},
                                     {"bias_in_weights": True}])
def test_a_wrong_convention_would_be_caught(params, batch, variant):
    got = np.asarray(ds.forward(params, batch["inputs"], CFG))
    wrong = np.asarray(kanana_ref.logits(params, batch["inputs"],
                                         settings(CFG), **variant))
    assert np.abs(got - wrong).max() > 20 * ATOL


# --------------------------------------------------------- the share test
def _sparse_layer(params, layer=0):
    return jax.tree_util.tree_map(lambda a: a[layer], params["moe_blocks"])


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """Over every share of the 8 routed experts (4 or 2 chips, each
    holding ``held`` of them): the routed parts summed, with the shared
    expert, attention and the residual counted once, are the uncut
    reference's output of the layer."""
    whole = dataclasses.replace(CFG, n_held_experts=8)
    params = random_tree(whole)
    lp = _sparse_layer(params)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 24, 64)),
                    jnp.float32)
    want, _ = kanana_ref.layer(x, lp, settings(whole), sparse=True)

    def program(first, n, keep=1.0):
        cfg = dataclasses.replace(CFG, n_held_experts=n,
                                  first_held_expert=first)
        mine = {**lp, "experts": {k: v[first:first + n] * keep
                                  for k, v in lp["experts"].items()}}
        return ds._block(x, mine, cfg, sparse=True)[0]

    # what every chip computes alike: the layer without any routed part
    none = program(0, held, keep=0.0)
    total = none
    for first in range(0, 8, held):
        total = total + (program(first, held) - none)
    assert np.abs(np.asarray(total - want)).max() < ATOL
    assert np.abs(np.asarray(none - want)).max() > 50 * ATOL
    # a share alone is not the layer
    assert np.abs(np.asarray(program(0, held) - want)).max() > 20 * ATOL


def test_a_share_equals_the_reference_given_the_same_share(params):
    """Held experts 2..5 of 8 (a share that does not start at 0)."""
    cfg = dataclasses.replace(CFG, first_held_expert=2)
    lp = _sparse_layer(params)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(2, 24, 64)),
                    jnp.float32)
    want, _ = kanana_ref.layer(x, lp, settings(cfg), sparse=True)
    got, stats = ds._block(x, lp, cfg, sparse=True)
    assert np.abs(np.asarray(got - want)).max() < ATOL
    assert 0 < float(stats.choice_share_held) < 1
    assert float(stats.held_rows) == float(stats.choice_share_held) * 48 * 2


# ------------------------------------------------------ the selection bias
def _route(x, w_router, bias):
    return moe.route_sigmoid(x, w_router, bias, 2, 2.5)


def test_the_bias_changes_the_choice_and_never_a_weight():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    idx0, w0 = _route(x, w, jnp.zeros(8))
    bias = jnp.asarray(rng.normal(size=8) * 0.3, jnp.float32)
    idx1, w1 = _route(x, w, bias)
    assert (np.asarray(idx0) != np.asarray(idx1)).any()
    # the weights are the chosen experts' own scores, renormalised, scaled
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    chosen = np.take_along_axis(scores, np.asarray(idx1), -1)
    want = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    assert np.abs(np.asarray(w1) - want).max() < 1e-6
    assert np.allclose(np.asarray(w1).sum(-1), 2.5, atol=1e-5)
    # a bias that moves every score alike changes nothing
    idx2, w2 = _route(x, w, jnp.full(8, 0.7))
    assert (np.asarray(idx2) == np.asarray(idx0)).all()
    assert np.abs(np.asarray(w2) - np.asarray(w0)).max() == 0
    # and no gradient reaches it
    g = jax.grad(lambda b: _route(x, w, b)[1].sum())(bias)
    assert np.abs(np.asarray(g)).max() == 0


def _forced(params, bias):
    """The tree with every sparse layer's selection bias replaced."""
    blocks = params["moe_blocks"]
    router = {**blocks["router"], "select_bias": jnp.broadcast_to(
        jnp.asarray(bias, jnp.float32), blocks["router"]["select_bias"].shape)}
    return {**params, "moe_blocks": {**blocks, "router": router}}


def test_tokens_whose_choices_are_all_absent_get_the_shared_expert_alone(
        params, batch):
    """A bias that sends every token to experts 6 and 7, held elsewhere:
    the routed part is zero, the layer is attention + shared expert, and
    the gradient is finite and reaches the router not at all."""
    away = _forced(params, [0, 0, 0, 0, 0, 0, 50, 50])
    got = np.asarray(ds.forward(away, batch["inputs"], CFG))
    want = np.asarray(kanana_ref.logits(away, batch["inputs"],
                                        settings(CFG)))
    assert np.abs(got - want).max() < ATOL
    no_experts = {**away, "moe_blocks": {**away["moe_blocks"], "experts": {
        k: jnp.zeros_like(v) for k, v in
        away["moe_blocks"]["experts"].items()}}}
    assert np.abs(np.asarray(ds.forward(no_experts, batch["inputs"], CFG))
                  - got).max() == 0
    _, stats = ds.forward_hidden(away, batch["inputs"], CFG)
    assert float(stats.held_rows.sum()) == 0
    g = jax.grad(ds.loss_fn)(away, batch, CFG)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(a)).all() for a in leaves)
    assert np.abs(np.asarray(g["moe_blocks"]["experts"]["w_up"])).max() == 0
    assert np.abs(np.asarray(g["moe_blocks"]["shared"]["w_up"]["kernel"])
                  ).max() > 0


def test_no_row_is_dropped_among_the_held_under_a_skewed_router(params,
                                                                batch):
    """A bias that sends every token to held expert 1 (and to 0 or 2-7 by
    its scores): one held expert takes a row of every token, far beyond
    any capacity, and every one is computed."""
    skewed = _forced(params, [0, 50, 0, 0, 0, 0, 0, 0])
    got = np.asarray(ds.forward(skewed, batch["inputs"], CFG))
    want = np.asarray(kanana_ref.logits(skewed, batch["inputs"],
                                        settings(CFG)))
    assert np.abs(got - want).max() < ATOL
    _, stats = ds.forward_hidden(skewed, batch["inputs"], CFG)
    n = batch["inputs"].size
    assert (np.asarray(stats.held_rows) >= n).all()
    assert (np.asarray(stats.load_max_over_mean) > 2).all()
    # taking expert 1 away changes the logits: its rows were computed
    ex = skewed["moe_blocks"]["experts"]
    cut = {**skewed, "moe_blocks": {**skewed["moe_blocks"], "experts": {
        k: v.at[:, 1].set(0) for k, v in ex.items()}}}
    off = np.asarray(ds.forward(cut, batch["inputs"], CFG))
    assert np.abs(off - got).max() > 20 * ATOL


def test_the_bias_is_untouched_by_a_step_with_weight_decay(batch):
    """Through build_train_program with weight decay on, as the cell
    trains (bf16 moments): the step reports what the held experts saw,
    moves every other leaf and leaves the bias bit for bit."""
    from ray_tpu.parallel import mesh as mesh_lib, spmd
    cfg = dataclasses.replace(CFG, remat=True)
    for moments in (jnp.bfloat16,):
        prog = spmd.build_train_program(
            loss_fn=lambda p, b: ds.loss_fn(p, b, cfg),
            init_params_fn=lambda rng: ds.init_params(rng, cfg),
            optimizer=spmd.default_optimizer(weight_decay=0.1, warmup=1,
                                             moments_dtype=moments),
            mesh=mesh_lib.single_device_mesh())
        state = prog.init_fn(jax.random.key(0))
        before = jax.tree_util.tree_map(np.asarray, state.params)
        for _ in range(2):
            state, metrics = prog.step_fn(state, spmd.shard_batch(prog, batch))
        after = jax.tree_util.tree_map(np.asarray, state.params)
        assert np.isfinite(float(metrics["loss"]))
        for name in ("moe_held_rows", "moe_held_load_max_over_mean",
                     "moe_choice_share_held", "moe_tile_fill"):
            assert float(metrics[name]) > 0, name
        assert 0 < float(metrics["moe_choice_share_held"]) < 1
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(before),
                jax.tree_util.tree_leaves(after)):
            same = (a == b).all()
            assert same == ("select_bias" in jax.tree_util.keystr(path)), path


# ------------------------------------------------------- what the ops chose
@pytest.mark.parametrize("shape,tiling", [
    ((65536, 2048, 1024), (512, 1024, 1024)),       # OLMoE's gate and up
    ((65536, 1024, 2048), (512, 1024, 1024)),       # OLMoE's down
    # a group's whole matrix beside 256 rows, where VMEM holds it (PR 62)
    ((98304, 2048, 768), (256, 2048, 768)),         # Kanana's 768-wide
    ((98304, 768, 2048), (256, 768, 2048)),
    ((163840, 2048, 512), (256, 2048, 512)),        # Qwen3-Next's 512-wide
    ((163840, 512, 2048), (256, 512, 2048)),
    ((163840, 2048, 512, 4), (512, 1024, 512)),     # not in float32
    # the serving cells' experts are too large whole: as they were
    ((512, 2560, 768), (512, 512, 768)),            # Ling's decode step
    ((16384, 2560, 768), (512, 512, 768)),          # and its chunk
    ((4096, 2048, 1536), (512, 1024, 768)),         # LFM2's 1,024 prompt
    ((8192, 3072, 3072), (512, 1024, 1024)),        # Trinity's chunk
    ((64, 3072, 3072), None),                       # a decode step's rows
    ((128, 2048, 1536), None),
    ((100, 64, 32), None),                          # a test's size
    ((98304, 2048, 700), None),
])
def test_the_grouped_matmuls_tile_follows_the_shape(shape, tiling):
    got = moe.gmm_tiling(*shape)
    assert got == tiling
    if got:
        m, d, f, *size = shape
        assert m % got[0] == 0 and d % got[1] == 0 and f % got[2] == 0
        # the rows' gradient contracts f and puts out d: its own tile
        assert moe.gmm_tiling(m, f, d, *size) == (got[0], got[2], got[1])
        assert got[0] in moe._GMM_ROW_TILES
        if got[1:] == (d, f):       # whole: within Mosaic's default limit
            assert moe._gmm_vmem_bytes(*got, *(size or [2])) < 15 * 2 ** 20


def test_flash_attention_takes_keys_wider_than_values():
    """The kernel (interpret mode here) at key width 24 and value width
    16 against dense attention: output and all three gradients, scaled by
    1 / sqrt(the KEY width)."""
    from ray_tpu.ops.attention import causal_attention, dense_attention
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.normal(size=(2, 256, 2, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 16)), jnp.float32)
    want = dense_attention(q, k, v)
    got = causal_attention(q, k, v, impl="flash")
    assert got.shape == (2, 256, 2, 16)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    probe = jnp.asarray(rng.normal(size=got.shape), jnp.float32)
    g_got = jax.grad(lambda *a: (causal_attention(*a, impl="flash")
                                 * probe).sum(), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: (dense_attention(*a) * probe).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        assert np.abs(np.asarray(a - b)).max() < 2e-4


# ------------------------------------- the model on the five-operand kernels
KERNEL_CFG = dataclasses.replace(CFG, attn_impl="flash")    # interpret mode


def test_the_model_on_the_kernels_equals_the_reference(params, batch):
    """32 positions are one tile: the flash kernels take q_nope, q_rope,
    k_nope, the one rotary key and v as the projections made them, and the
    logits are the float32 reference's."""
    want = np.asarray(kanana_ref.logits(params, batch["inputs"],
                                        settings(CFG)))
    got = np.asarray(ds.forward(params, batch["inputs"], KERNEL_CFG))
    assert np.abs(got - want).max() < ATOL
    jaxpr = str(jax.make_jaxpr(lambda p, t: ds.forward(p, t, KERNEL_CFG))(
        params, batch["inputs"]))
    assert "name=flash_fwd" in jaxpr


@pytest.fixture(scope="module")
def kernel_grads(params, batch):
    return _flat(jax.grad(ds.loss_fn)(params, batch, KERNEL_CFG))


@pytest.mark.parametrize("group", ["wq", "wkv_a", "kv_norm", "wkv_b", "wo",
                                   "attn_norm", "wte"])
def test_gradients_through_the_kernels_equal_the_references(
        grads, kernel_grads, group):
    """What the backward kernel's five results reach: W_q by column group,
    W_kv_a (its rotary columns through the gradient summed over the
    heads), W_kv_b, and everything below."""
    _, want = grads
    keys = [k for k in want if f"'{group}" in k]
    assert keys
    for key in keys:
        scale = np.abs(want[key]).max()
        assert np.abs(kernel_grads[key] - want[key]).max() \
            < 1e-4 * scale + 1e-7, key


@pytest.mark.parametrize("axes", [{"fsdp": 2, "tensor": 2},
                                  {"data": 2, "expert": 2}, {"data": 1}])
def test_the_mesh_rules_place_every_new_leaf(axes):
    """Every leaf's spec fits its rank; matrices name ``tensor`` or
    ``expert`` where the layout has them, and the four-chip layouts
    resolve to shardings that divide the tiny shapes or replicate."""
    from jax.sharding import PartitionSpec as P
    from ray_tpu.parallel import mesh as mesh_lib
    shapes = jax.eval_shape(lambda r: ds.init_params(r, CFG),
                            jax.random.key(0))
    specs = mesh_lib.param_specs(shapes)
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))}
    moe_blocks = "['moe_blocks']"
    assert flat[f"{moe_blocks}['wq']['kernel']"] == P("pipeline", "fsdp",
                                                    "tensor")
    assert flat[f"{moe_blocks}['wkv_a']['kernel']"] == P("pipeline", "fsdp",
                                                       None)
    assert flat[f"{moe_blocks}['wo']['kernel']"] == P("pipeline", "tensor",
                                                    "fsdp")
    assert flat[f"{moe_blocks}['experts']['w_gate']"] == \
        P("pipeline", "expert", "fsdp", "tensor")
    assert flat[f"{moe_blocks}['shared']['w_down']['kernel']"] == \
        P("pipeline", "tensor", "fsdp")
    assert flat[f"{moe_blocks}['router']['select_bias']"] == P("pipeline",
                                                             None)
    assert flat["['dense_blocks']['w_up']['kernel']"] == \
        P("pipeline", "fsdp", "tensor")
    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    mc = mesh_lib.MeshConfig(**axes).resolved(n)
    mesh = mesh_lib.build_mesh(mc, jax.devices()[:n])
    shardings = mesh_lib.named_shardings(mesh, specs, shapes)
    for leaf, sh in zip(jax.tree_util.tree_leaves(shapes),
                        jax.tree_util.tree_leaves(shardings)):
        sh.shard_shape(leaf.shape)          # raises where it does not divide
