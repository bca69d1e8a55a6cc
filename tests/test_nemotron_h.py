"""models/nemotron_h.py (one mixer a layer in an order without a period:
Mamba-2, relu^2 experts with no gate matrix behind a sigmoid router,
attention without positions) against its plain float32 reference at a
tiny size on the CPU, and the contracts of what it forced: every wrong
convention the reference can name is caught, a later token changes no
earlier logit, the eight shares add up to the uncut layer, the pattern
builds its blocks in order and refuses a dense layer, the mesh rules."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import nemotron_h_ref as ref
from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops import moe

# hidden 48; five layers MEM*E of the cell's nine (every kind, two of a
# stack: the suite's time is their compiles); 4 mixer heads of 6 on 2
# groups of 8 state columns, chunks of 8; 4 / 2 attention heads of 8; 8
# routed experts of width 20, 4 held, 3 a token; shared 28
CFG = dataclasses.replace(nh.tiny(vocab=211, seq=48, pattern="MEM*E"),
                          dtype=jnp.float32, remat=False)
# both sides compute in float32, so they differ by the order of sums only
ATOL = 2e-4


def settings(cfg):
    first = cfg.first_held_expert
    return {"hybrid_override_pattern": cfg.pattern,
            "mamba_num_heads": cfg.ssm_heads,
            "mamba_head_dim": cfg.ssm_head_dim, "n_groups": cfg.ssm_groups,
            "ssm_state_size": cfg.ssm_state, "chunk_size": cfg.ssm_chunk,
            "layer_norm_epsilon": cfg.rms_eps,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scale,
            "held_expert_ids": list(range(first,
                                          first + cfg.n_held_experts))}


def random_tree(cfg, seed=3):
    """The init with what it leaves at a constant moved off it: norm
    weights and ``D`` away from 1 and the selection bias away from 0 (as
    large as the scores' spread, so that it decides choices)."""
    rng = np.random.default_rng(seed + 2)

    def leaf(path, a):
        key = jax.tree_util.keystr(path)
        if "scale" in key or key.endswith("['D']"):
            return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        if "select_bias" in key:
            return jnp.asarray(rng.normal(0, 0.05, a.shape), a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(
        leaf, jax.jit(nh.init_params, static_argnums=1)(jax.random.key(seed),
                                                        cfg))


forward = jax.jit(nh.forward, static_argnums=2)
loss_fn = jax.jit(nh.loss_fn, static_argnums=2)
grad_fn = jax.jit(jax.grad(nh.loss_fn), static_argnums=2)


@pytest.fixture(scope="module")
def params():
    return random_tree(CFG)


@pytest.fixture(scope="module")
def batch():
    # 33 positions: no whole number of chunks of 8
    toks = np.random.default_rng(1).integers(0, CFG.vocab_size, (2, 34))
    return {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32)}


def _flat(tree):
    return {jax.tree_util.keystr(p): np.asarray(a)
            for p, a in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def program_logits(params, batch):
    return np.asarray(forward(params, batch["inputs"], CFG))


@pytest.fixture(scope="module")
def grads(params, batch):
    got = grad_fn(params, batch, CFG)
    want = jax.grad(ref.loss)(params, batch["inputs"], batch["targets"],
                              settings(CFG))
    return _flat(got), _flat(want)


def test_logits_and_loss_equal_the_references(params, batch, program_logits):
    want = np.asarray(ref.logits(params, batch["inputs"], settings(CFG)))
    assert np.abs(program_logits - want).max() < ATOL
    assert np.abs(want).max() > 0.5             # not all-zero logits
    loss = float(loss_fn(params, batch, CFG))
    assert loss == pytest.approx(float(ref.loss(
        params, batch["inputs"], batch["targets"], settings(CFG))), abs=1e-5)
    assert abs(loss - np.log(CFG.vocab_size)) < 1.0


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_wrong_convention_is_caught(params, batch, program_logits, variant):
    """The reference computed with ONE convention wrong lies far outside
    the tolerance of the comparison above: had the program that mistake,
    the comparison would fail."""
    wrong = np.asarray(ref.logits(params, batch["inputs"], settings(CFG),
                                  variant=variant))
    assert np.abs(program_logits - wrong).max() > 50 * ATOL


def test_every_listed_wrong_convention_has_a_case():
    assert set(ref.VARIANTS) == {
        "state_not_carried", "norm_then_gate", "norm_over_all",
        "no_conv_bias", "no_skip", "rotary", "relu_not_squared",
        "bias_weighs", "no_scale"}


GROUPS = ["wte", "lm_head", "norm_f", "norm'", "in_proj", "conv", "A_log",
          "D'", "dt_bias", "ssm_norm", "out_proj", "router", "experts",
          "shared", "wq", "wk", "wv", "wo"]


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_equal_the_references(grads, group):
    """Every leaf of the group to 1e-4 of the leaf's largest gradient;
    the selection bias has none on either side."""
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


def test_the_step_reports_what_the_experts_saw_and_how_the_states_forget(
        params, batch):
    from ray_tpu.parallel import spmd

    def told(params, batch):
        with spmd._collect_step_metrics() as sink:
            nh.loss_fn(params, batch, CFG)
        return dict(sink)
    sink = jax.jit(told)(params, batch)
    n = batch["inputs"].size
    assert set(sink) == {"moe_held_rows", "moe_held_load_max_over_mean",
                         "moe_choice_share_held", "moe_tile_fill",
                         "ssm_decay_mean"}
    assert 0 < float(sink["moe_held_rows"]) < n * CFG.experts_per_token
    assert float(sink["moe_choice_share_held"]) == pytest.approx(
        float(sink["moe_held_rows"]) / (n * CFG.experts_per_token))
    # no megablox tile divides a test's rows: nothing of ours to fit
    assert float(sink["moe_tile_fill"]) == 1.0
    # dt in (0.001, 0.1), A in (1, 16): the states remember
    assert 0.5 < float(sink["ssm_decay_mean"]) < 1.0


def test_remat_changes_no_value(params, batch, grads):
    cfg = dataclasses.replace(CFG, remat=True, remat_policy="full")
    got = _flat(grad_fn(params, batch, cfg))
    for key, want in grads[0].items():
        np.testing.assert_allclose(got[key], want,
                                   atol=1e-5 * np.abs(want).max() + 1e-8)


def test_a_later_token_changes_no_earlier_logit(params, batch,
                                                program_logits):
    """Every mixer is causal: the conv's taps, the scan, the attention's
    mask; the experts and norms are a token's own."""
    at = 19
    changed = batch["inputs"].at[:, at].set(
        (batch["inputs"][:, at] + 1) % CFG.vocab_size)
    got = np.asarray(forward(params, changed, CFG))
    np.testing.assert_array_equal(got[:, :at], program_logits[:, :at])
    assert np.abs(got[:, at:] - program_logits[:, at:]).max() > 1e-3


def test_the_mixer_through_the_scans_kernels_equals_the_reference(
        monkeypatch):
    """On the CPU the model takes ``ssd_scan`` under autodiff; here
    ``_mamba`` runs the training scan's two Pallas kernels (PR 74) in
    interpret mode at a shape their rule takes (4 heads of 64 on 2 groups
    of 128 state columns, 2 chunks of 128) and is held to the reference's
    token-by-token mixer: values, and the gradients of the hidden states
    and of every leaf of the layer."""
    from ray_tpu.ops import ssm
    cfg = dataclasses.replace(
        CFG, pattern="M", n_embd=32, ssm_heads=4, ssm_head_dim=64,
        ssm_groups=2, ssm_state=128, ssm_chunk=128)
    tree = random_tree(cfg)["mamba_blocks"]
    lp = jax.tree_util.tree_map(lambda a: a[0], tree)
    u = jax.random.normal(jax.random.key(5), (2, 256, cfg.n_embd))
    probe = jax.random.normal(jax.random.key(6), u.shape)
    ran, kernels = [], ssm.ssd_scan_kernels

    def interpreted(xbc, *rest):
        ran.append(xbc.shape)
        return kernels(xbc, *rest, interpret=True)
    monkeypatch.setattr(ssm, "_scan_kernels_run", lambda *a: True)
    monkeypatch.setattr(ssm, "ssd_scan_kernels", interpreted)

    def ours(u, lp):
        return (nh._mamba(u, lp, cfg)[0] * probe).sum()

    def theirs(u, lp):
        mixed = jnp.stack([ref.mamba_mixer(
            row, lp, heads=4, head_dim=64, groups=2, state=128,
            eps=cfg.rms_eps, chunk=128)[0] for row in u])
        return (mixed * probe).sum()
    with jax.default_matmul_precision("highest"):
        got_v, got = jax.value_and_grad(ours, (0, 1))(u, lp)
        want_v, want = jax.value_and_grad(theirs, (0, 1))(u, lp)
    assert ran == [(2, 256, 4 * 64 + 2 * 2 * 128)]
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-4)
    got, want = _flat(got), _flat(want)
    assert set(got) == set(want) and len(got) == 10
    for key, w in want.items():
        scale = np.abs(w).max()
        if "['norm']" in key:           # the block's, applied before it
            assert scale == 0 and np.abs(got[key]).max() == 0
            continue
        assert scale > 0, key
        assert np.abs(got[key] - w).max() < 2e-4 * scale, key


# -------------------------------------------------------------- the pattern
def test_the_cells_pattern_builds_its_blocks_in_that_order():
    cfg = nh.tiny()
    assert cfg.pattern == "MEMEM*EME" and cfg.n_layer == 9
    assert (cfg.count("M"), cfg.count("E"), cfg.count("*")) == (4, 4, 1)
    shapes = jax.eval_shape(lambda r: nh.init_params(r, cfg),
                            jax.random.key(0))
    assert shapes["mamba_blocks"]["in_proj"]["kernel"].shape[0] == 4
    assert shapes["expert_blocks"]["router"]["kernel"].shape[0] == 4
    assert shapes["attn_blocks"]["wq"]["kernel"].shape[0] == 1
    assert "w_gate" not in shapes["expert_blocks"]["experts"]
    assert "w_gate" not in shapes["expert_blocks"]["shared"]
    order = [kind for kind, _ in ref.layers_of(
        jax.tree_util.tree_map(lambda s: np.zeros(s.shape[:1]), shapes),
        {"hybrid_override_pattern": cfg.pattern})]
    assert "".join(order) == "MEMEM*EME"
    whole = nh.NemotronHConfig()
    assert (whole.n_layer, whole.count("M"), whole.count("E"),
            whole.count("*")) == (52, 23, 23, 6)
    assert whole.pattern.startswith(cfg.pattern)


@pytest.mark.parametrize("pattern", ["ME-M*", "-", "MEX", ""])
def test_a_layer_the_program_does_not_have_is_refused(pattern):
    with pytest.raises(ValueError, match="dense MLP" if "-" in pattern
                       else "a layer is one of"):
        nh.tiny(pattern=pattern)


def test_the_layers_run_in_the_patterns_order(params, batch):
    """Another order of the same blocks is another function."""
    other = dataclasses.replace(CFG, pattern="ME*ME")
    assert (other.count("M"), other.count("E"), other.count("*")) == (2, 2, 1)
    got = np.asarray(forward(params, batch["inputs"], other))
    want = np.asarray(ref.logits(params, batch["inputs"], settings(other)))
    assert np.abs(got - want).max() < ATOL
    assert np.abs(got - np.asarray(forward(params, batch["inputs"], CFG))
                  ).max() > 50 * ATOL


# --------------------------------------------------------------- the share
def _layer(params, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["expert_blocks"])


def _share_of(lp, first, held):
    """A layer's leaves with experts ``first .. first + held - 1``."""
    return {**lp, "experts": {w: a[first:first + held]
                              for w, a in lp["experts"].items()}}


@pytest.fixture(scope="module")
def uncut():
    """A tree that holds all 8 experts, and normed hidden states."""
    cfg = dataclasses.replace(CFG, n_held_experts=CFG.n_routed_experts)
    h = jax.random.normal(jax.random.key(11), (2, 20, cfg.n_embd))
    return cfg, _layer(random_tree(cfg, seed=5)), h


def _reference_layer(h, lp, held_ids):
    with jax.default_matmul_precision("highest"):
        return ref.moe(h.reshape(-1, h.shape[-1]), lp,
                       {**settings(CFG), "held_expert_ids": held_ids}
                       ).reshape(h.shape)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(uncut, held):
    """Every share's routed part (eight shares of one expert, four of
    two, two of four) and the shared expert ONCE equal the uncut
    reference's layer: a token's weights are normalised over all the
    picks, held or not, so no share knows or needs the others."""
    cfg, lp, h = uncut
    nothing = _reference_layer(h, _share_of(lp, 0, 0), [])   # shared alone
    total = nothing
    for first in range(0, cfg.n_routed_experts, held):
        share = dataclasses.replace(cfg, n_held_experts=held,
                                    first_held_expert=first)
        out, stats = nh._experts(h, _share_of(lp, first, held), share)
        assert isinstance(stats, moe.HeldStats)
        total = total + (out - nothing)
    want = _reference_layer(h, lp, list(range(cfg.n_routed_experts)))
    np.testing.assert_allclose(total, want, atol=ATOL)
    assert np.abs(want - nothing).max() > 0.1


def test_the_bias_decides_the_choice_and_never_a_weight(uncut):
    """A bias that lifts experts 6 and 7 over every score sends every
    token to them; their weights are still the scores' own."""
    cfg, lp, h = uncut
    bias = jnp.zeros((cfg.n_routed_experts,)).at[6:].set(5.0)
    lifted = {**lp, "router": {**lp["router"], "select_bias": bias}}
    share = dataclasses.replace(cfg, n_held_experts=2, first_held_expert=6)
    out, stats = nh._experts(h, _share_of(lifted, 6, 2), share)
    n = h.shape[0] * h.shape[1]
    assert float(stats.held_rows) == 2 * n
    want = _reference_layer(h, _share_of(lifted, 6, 2), [6, 7])
    np.testing.assert_allclose(out, want, atol=ATOL)
    scores = jax.nn.sigmoid(h.reshape(n, -1) @ lp["router"]["kernel"])
    idx, weights = moe.route_sigmoid(
        h.reshape(n, -1), lp["router"]["kernel"], bias,
        cfg.experts_per_token, cfg.routed_scale)
    chosen = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        weights, cfg.routed_scale * chosen / chosen.sum(-1, keepdims=True),
        atol=1e-6)


# ------------------------------------------------------------ the mesh rules
def test_the_mesh_rules_place_every_new_leaf():
    """Every leaf's spec fits its rank and nothing of the three stacks
    falls through to the catch-all rule."""
    from jax.sharding import PartitionSpec as P
    from ray_tpu.parallel import mesh as mesh_lib
    shapes = jax.eval_shape(lambda r: nh.init_params(r, CFG),
                            jax.random.key(0))
    specs = mesh_lib.param_specs(shapes)
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))}
    m, e, a = "['mamba_blocks']", "['expert_blocks']", "['attn_blocks']"
    want = {
        f"{m}['in_proj']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{m}['out_proj']['kernel']": P("pipeline", "tensor", "fsdp"),
        f"{m}['conv']['kernel']": P("pipeline", None, "tensor"),
        f"{m}['conv']['bias']": P("pipeline", "tensor"),
        f"{m}['A_log']": P("pipeline", "tensor"),
        f"{m}['ssm_norm']['scale']": P("pipeline", "tensor"),
        f"{e}['experts']['w_up']": P("pipeline", "expert", "fsdp", "tensor"),
        f"{e}['experts']['w_down']": P("pipeline", "expert", "tensor",
                                       "fsdp"),
        f"{e}['shared']['w_up']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{e}['shared']['w_down']['kernel']": P("pipeline", "tensor", "fsdp"),
        f"{e}['router']['select_bias']": P("pipeline", None),
        f"{a}['wq']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{a}['wo']['kernel']": P("pipeline", "tensor", "fsdp"),
    }
    for key, spec in want.items():
        assert tuple(flat[key])[:len(spec)] == tuple(spec), key
    for key, spec in flat.items():
        if key.startswith((m, e, a)):
            assert tuple(spec)[0] == "pipeline", key
