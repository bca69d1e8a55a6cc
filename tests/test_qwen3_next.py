"""models/qwen3_next.py (three Gated DeltaNet layers to one gated full
attention layer, softmax-routed held experts beside a gated shared one)
against its plain float32 reference at a tiny size on the CPU, and the
contracts of what it forced: every wrong convention the reference can
name is caught, the eight shares add up to the uncut layer, a token none
of whose picks is held gets the shared expert alone, dropless among the
held, the renormalised softmax choice, a softmax share's statistics, the
one-list form of the rows' way back, the mesh rules."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import qwen3_next_ref as ref
from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops import moe

# hidden 64; two periods of 3 DeltaNet + 1 attention layers; 2 / 4 rule
# heads of 8 / 12, chunks of 8; 2 / 1 attention heads of 16, 8 lanes
# rotated; 8 routed experts of width 24, 4 held, 3 a token; shared 20
CFG = dataclasses.replace(qn.tiny(vocab=211, seq=48), dtype=jnp.float32,
                          remat=False)
# both sides compute in float32, so they differ by the order of sums only
ATOL = 2e-4


def settings(cfg):
    first = cfg.first_held_expert
    return {"num_hidden_layers": cfg.n_layer,
            "full_attention_interval": cfg.attn_interval,
            "linear_num_key_heads": cfg.gdn_key_heads,
            "linear_num_value_heads": cfg.gdn_value_heads,
            "linear_key_head_dim": cfg.gdn_key_dim,
            "linear_value_head_dim": cfg.gdn_value_dim,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
            "num_experts_per_tok": cfg.experts_per_token,
            "rule_chunk": cfg.rule_chunk,
            "held_expert_ids": list(range(first,
                                          first + cfg.n_held_experts))}


def random_tree(cfg, seed=3):
    """Matrices five times the init (so that routing is decisive and the
    logits are O(1)), zero-centred norm weights away from 0 and the plain
    one's away from 1; the conv, A_log and dt_bias as drawn."""
    rng = np.random.default_rng(seed + 2)

    def leaf(path, a):
        key = jax.tree_util.keystr(path)
        if "out_norm" in key:
            return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        if "scale" in key:
            return jnp.asarray(rng.uniform(-0.5, 0.5, a.shape), a.dtype)
        if "A_log" in key or "dt_bias" in key or "conv" in key:
            return a
        return a * 5
    return jax.tree_util.tree_map_with_path(
        leaf, jax.jit(qn.init_params, static_argnums=1)(jax.random.key(seed),
                                                        cfg))


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
    return np.asarray(qn.forward(params, batch["inputs"], CFG))


@pytest.fixture(scope="module")
def grads(params, batch):
    got = jax.grad(qn.loss_fn)(params, batch, CFG)
    want = jax.grad(ref.loss)(params, batch["inputs"], batch["targets"],
                              settings(CFG))
    return _flat(got), _flat(want)


def test_logits_and_loss_equal_the_references(params, batch, program_logits):
    want = np.asarray(ref.logits(params, batch["inputs"], settings(CFG)))
    assert np.abs(program_logits - want).max() < ATOL
    assert np.abs(want).max() > 0.5             # not all-zero logits
    loss = float(qn.loss_fn(params, batch, CFG))
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
    assert np.abs(program_logits - wrong).max() > 500 * ATOL


def test_every_listed_wrong_convention_has_a_case():
    assert set(ref.VARIANTS) == {
        "no_decay", "no_beta", "state_not_carried", "no_l2norm",
        "gate_before_norm", "plain_norm", "rope_all_lanes", "no_attn_gate",
        "no_shared_gate", "no_renorm"}


GROUPS = ["wte", "lm_head", "norm_f", "mixer_norm", "mlp_norm", "router",
          "experts", "shared'", "shared_gate", "in_proj_qkvz", "in_proj_ba",
          "conv", "A_log", "dt_bias", "out_norm", "out_proj", "wq", "wk",
          "wv", "q_norm", "k_norm", "wo"]


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_equal_the_references(grads, group):
    """Every leaf of the group to 1e-4 of the leaf's largest gradient."""
    got, want = grads
    keys = [k for k in want if f"'{group}" in k]
    assert keys, (group, sorted(want))
    for key in keys:
        scale = np.abs(want[key]).max()
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
    with spmd._collect_step_metrics() as sink:
        qn.loss_fn(params, batch, CFG)
    n = batch["inputs"].size
    assert set(sink) == {"moe_held_rows", "moe_held_load_max_over_mean",
                         "moe_choice_share_held", "moe_tile_fill",
                         "gdn_decay_mean"}
    assert 0 < float(sink["moe_held_rows"]) < n * CFG.experts_per_token
    assert float(sink["moe_choice_share_held"]) == pytest.approx(
        float(sink["moe_held_rows"]) / (n * CFG.experts_per_token))
    # no megablox tile divides a test's rows: nothing of ours to fit
    assert float(sink["moe_tile_fill"]) == 1.0
    # dt in (0.001, 0.1), A in (0, 16): the states remember
    assert 0.5 < float(sink["gdn_decay_mean"]) < 1.0


def test_remat_changes_no_value(params, batch):
    cfg = dataclasses.replace(CFG, remat=True, remat_policy="full")
    want = jax.grad(qn.loss_fn)(params, batch, CFG)
    got = jax.grad(qn.loss_fn)(params, batch, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(b).max() + 1e-8)


# --------------------------------------------------------------- the share
def _layer(params, kind="gdn", i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params[f"{kind}_blocks"])


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
                       {"num_experts_per_tok": CFG.experts_per_token,
                        "held_expert_ids": held_ids}).reshape(h.shape)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(uncut, held):
    """Every share's routed part and the shared expert ONCE equal the
    uncut reference's layer: a token's weights are normalised over all
    the picks, held or not, so no share knows or needs the others."""
    cfg, lp, h = uncut
    nothing = _reference_layer(h, _share_of(lp, 0, 0), [])   # shared alone
    total = nothing
    for first in range(0, cfg.n_routed_experts, held):
        share = dataclasses.replace(cfg, n_held_experts=held,
                                    first_held_expert=first)
        out, stats = qn._experts(h, _share_of(lp, first, held), share)
        assert isinstance(stats, moe.HeldStats)
        total = total + (out - nothing)
    want = _reference_layer(h, lp, list(range(cfg.n_routed_experts)))
    np.testing.assert_allclose(total, want, atol=ATOL)
    assert np.abs(want - nothing).max() > 0.1


def _steered(lp, cfg, onto, along=0):
    """The layer with a router that sends every token whose hidden state
    is positive in lane ``along`` to experts ``onto`` and every other to
    the rest."""
    kernel = np.full((cfg.n_embd, cfg.n_routed_experts), 0.0, np.float32)
    kernel[along] = -50.0
    kernel[along, list(onto)] = 50.0
    return {**lp, "router": {"kernel": jnp.asarray(kernel)}}


def test_a_token_none_of_whose_picks_is_held_gets_the_shared_expert_alone(
        uncut):
    cfg, lp, h = uncut
    share = dataclasses.replace(cfg, n_held_experts=4, first_held_expert=4)
    away = _steered(lp, cfg, onto=(0, 1, 2))     # +e_0 tokens pick 0, 1, 2
    out, stats = qn._experts(h, _share_of(away, 4, 4), share)
    nothing = _reference_layer(h, _share_of(away, 0, 0), [])
    plus = np.asarray(h[..., 0] > 0)
    assert plus.any() and (~plus).any()
    np.testing.assert_allclose(np.asarray(out)[plus], np.asarray(nothing)[plus],
                               atol=ATOL)
    assert np.abs(np.asarray(out - nothing)[~plus]).max() > 0.05
    # the others pick 3 of the 5 experts 3 .. 7, all equal: top_k takes the
    # lowest ids, 3, 4, 5, of which two are held
    assert float(stats.held_rows) == 2 * (~plus).sum()


def test_no_row_is_dropped_among_the_held_under_a_skewed_router(uncut):
    cfg, lp, h = uncut
    share = dataclasses.replace(cfg, n_held_experts=4, first_held_expert=0)
    skewed = _steered(lp, cfg, onto=(0, 1, 2), along=1)
    h = h.at[..., 1].set(jnp.abs(h[..., 1]) + 0.1)   # every token: 0, 1, 2
    out, stats = qn._experts(h, _share_of(skewed, 0, 4), share)
    n = h.shape[0] * h.shape[1]
    assert float(stats.held_rows) == n * cfg.experts_per_token
    assert float(stats.choice_share_held) == 1.0
    assert float(stats.load_max_over_mean) == pytest.approx(4 / 3)
    want = _reference_layer(h, _share_of(skewed, 0, 4), [0, 1, 2, 3])
    np.testing.assert_allclose(out, want, atol=ATOL)


def test_the_softmax_choice_weighs_as_it_is_or_renormalised():
    x = jax.random.normal(jax.random.key(0), (16, 32))
    w = jax.random.normal(jax.random.key(1), (32, 8)) * 0.1
    idx, plain, _, probs = moe.route_softmax(x, w, 3)
    idx2, normed, _, _ = moe.route_softmax(x, w, 3, norm_topk=True)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_allclose(plain, jnp.take_along_axis(probs, idx, -1))
    np.testing.assert_allclose(normed.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(normed, plain / plain.sum(-1, keepdims=True),
                               atol=1e-7)
    assert float(plain.sum(-1).max()) < 0.9


def test_a_softmax_routed_share_reports_the_rows_it_held():
    n, d, f, k = 24, 16, 8, 2
    keys = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(keys[0], (n, d))
    w_router = jax.random.normal(keys[1], (d, 8))
    ws = [jax.random.normal(key, shape) * 0.3 for key, shape in zip(
        keys[2:], ((8, d, f), (8, d, f), (8, f, d)))]
    _, whole = moe.dropless_moe_ffn(x, w_router, *ws, k=k)
    assert isinstance(whole, moe.RouterStats)            # OLMoE's, as it was
    _, share = moe.dropless_moe_ffn(x, w_router, *(w[2:5] for w in ws), k=k,
                                    norm_topk=True, first_held=2)
    assert isinstance(share, moe.HeldStats)
    idx = np.asarray(moe.route_softmax(x, w_router, k)[0])
    assert float(share.held_rows) == ((idx >= 2) & (idx < 5)).sum()
    assert float(share.choice_share_held) == pytest.approx(
        float(share.held_rows) / (n * k))


# ---------------------------------------------------- the rows' way back
def test_the_cells_rows_meet_the_walks_limits_exactly(monkeypatch):
    """16,384 tokens x 2,048 x 2 B is exactly ``_SOURCE_BYTES``, the (N, d)
    source the way out holds in VMEM: the cell's layer walks its held
    rows, one more tile of tokens would not; and its 163,840 assignments
    are one list of the scalar memory, not two (Kanana's 98,304 are two)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = dict(k=10, d=2048, f=512, held=64, num_experts=512,
                dtype=jnp.bfloat16)
    assert 16384 * 2048 * 2 == moe._SOURCE_BYTES
    assert moe._walks_held_rows(16384, **cell)
    assert not moe._walks_held_rows(16384 + 256, **cell)
    assert not moe._walks_held_rows(16384, **{**cell, "dtype": jnp.float32})
    assert not moe._walks_held_rows(16384, **{**cell, "held": 512})
    assert moe._one_list(163840, 256) and not moe._one_list(98304, 256)
    assert 4 * 163840 <= moe._LIST_BYTES < 2 * 4 * 163840
    assert not moe._walks_held_rows(32768, **{**cell, "d": 1024})


@pytest.mark.parametrize("held_rows", [0, 37, 96])
def test_the_one_list_form_sums_the_same_rows(monkeypatch, held_rows):
    """``sum_held_slots`` handed its list as one int32 an entry (what the
    cell's 163,840 assignments force) against the two-array form."""
    n, k, d = 32, 3, 128
    rng = np.random.default_rng(held_rows)
    inverse = jnp.asarray(rng.permutation(n * k), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(n * k, d)), jnp.bfloat16)
    want = moe._sum_slots(rows, inverse, k, jnp.int32(held_rows))
    monkeypatch.setattr(moe, "_LIST_BYTES", 0)
    assert moe._one_list(n * k, moe._token_tile(n))
    moe.sum_held_slots.clear_cache()        # the form is chosen as it traces
    got = moe._sum_slots(rows, inverse, k, jnp.int32(held_rows))
    moe.sum_held_slots.clear_cache()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    if held_rows:
        assert np.abs(np.asarray(want, np.float32)).max() > 0


# ------------------------------------------------------------ the mesh rules
@pytest.mark.parametrize("axes", [{"fsdp": 2, "tensor": 2},
                                  {"data": 2, "expert": 2}, {"data": 1}])
def test_the_mesh_rules_place_every_new_leaf(axes):
    """Every leaf's spec fits its rank; matrices name ``tensor`` or
    ``expert`` where the layout has them, and the four-chip layouts
    resolve to shardings that divide the tiny shapes or replicate."""
    from jax.sharding import PartitionSpec as P
    from ray_tpu.parallel import mesh as mesh_lib
    shapes = jax.eval_shape(lambda r: qn.init_params(r, CFG),
                            jax.random.key(0))
    specs = mesh_lib.param_specs(shapes)
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))}
    gdn, attn = "['gdn_blocks']", "['attn_blocks']"
    want = {
        f"{gdn}['in_proj_qkvz']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{gdn}['in_proj_ba']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{gdn}['out_proj']['kernel']": P("pipeline", "tensor", "fsdp"),
        f"{gdn}['conv']['kernel']": P("pipeline", None, "tensor"),
        f"{gdn}['A_log']": P("pipeline", "tensor"),
        f"{gdn}['dt_bias']": P("pipeline", "tensor"),
        f"{attn}['wq']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{attn}['wk']['kernel']": P("pipeline", "fsdp", "tensor"),
        f"{attn}['wo']['kernel']": P("pipeline", "tensor", "fsdp"),
    }
    for kind in (gdn, attn):
        want[f"{kind}['experts']['w_gate']"] = P("pipeline", "expert", "fsdp",
                                                 "tensor")
        want[f"{kind}['experts']['w_down']"] = P("pipeline", "expert",
                                                 "tensor", "fsdp")
        want[f"{kind}['shared']['w_up']['kernel']"] = P("pipeline", "fsdp",
                                                        "tensor")
        want[f"{kind}['shared']['w_down']['kernel']"] = P("pipeline",
                                                          "tensor", "fsdp")
    for key, spec in want.items():
        assert tuple(flat[key])[:len(spec)] == tuple(spec), key
    # nothing of the two stacks falls through to the catch-all rule
    for key, spec in flat.items():
        if key.startswith((gdn, attn)):
            assert tuple(spec)[0] == "pipeline", key
    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    mc = mesh_lib.MeshConfig(**axes).resolved(n)
    mesh = mesh_lib.build_mesh(mc, jax.devices()[:n])
    shardings = mesh_lib.named_shardings(mesh, specs, shapes)
    for leaf, sh in zip(jax.tree_util.tree_leaves(shapes),
                        jax.tree_util.tree_leaves(shardings)):
        sh.shard_shape(leaf.shape)          # raises where it does not divide


# ------------------------------------------- the conv's kernels in the mixer
def test_the_mixers_conv_in_its_kernels_under_a_checkpoint(monkeypatch):
    """What the cell's DeltaNet block does with ``ops/ssm.causal_conv_silu``
    on the chip, here through the kernels' interpret mode at a conv of 128
    channels (2 key heads of 16, 4 value heads of 16, bf16 activations,
    two time blocks): the mixer under ``jax.checkpoint`` and ``jax.grad``
    runs the forward kernel twice (the pass and its recomputation) and the
    backward once, and value and every gradient, the taps' among them,
    stay the XLA form's to bf16's rounding."""
    from ray_tpu.ops import ssm
    cfg = dataclasses.replace(CFG, gdn_key_dim=16, gdn_value_dim=16,
                              dtype=jnp.bfloat16)
    assert cfg.conv_width == 128
    lp = _layer(random_tree(cfg, seed=7))
    u = jax.random.normal(jax.random.key(12), (2, 64, cfg.n_embd)) \
        .astype(cfg.dtype)
    probe = jax.random.normal(jax.random.key(13), u.shape)

    def loss(u, lp):
        out = jax.checkpoint(lambda u, lp: qn._gdn_mixer(u, lp, cfg)[0])(u, lp)
        return (out.astype(jnp.float32) * probe).sum()

    run = jax.value_and_grad(loss, argnums=(0, 1))
    want, want_grads = run(u, lp)
    monkeypatch.setattr(ssm, "CONV_ROWS", 32)
    monkeypatch.setattr(ssm, "CONV_STEP", 16)
    monkeypatch.setattr(ssm, "causal_conv_silu",
                        lambda x, w: ssm._conv_silu_kernels(x, w, True))
    program = str(jax.make_jaxpr(run)(u, lp))
    calls = re.findall(r"jit\[\s*name=(causal_conv_\w+)", program)
    assert sorted(calls) == ["causal_conv_bwd"] + ["causal_conv_fwd"] * 2
    got, got_grads = run(u, lp)
    assert abs(float(got) - float(want)) < 0.02 * abs(float(want)) + 0.05
    got_grads, want_grads = _flat(got_grads), _flat(want_grads)
    assert any("conv" in key for key in want_grads)
    for key, b in want_grads.items():
        if "experts" in key or "router" in key or "shared" in key \
                or "mlp_norm" in key or "mixer_norm" in key:
            continue                    # the mixer reads none of them
        a, b = np.float32(got_grads[key]), np.float32(b)
        assert np.abs(b).max() > 0, key
        np.testing.assert_allclose(a, b, atol=0.03 * np.abs(b).max(),
                                   err_msg=key)


# ----------------------------------------- the rule takes the conv's output
def _norms_and_slices(qkv, g, beta, heads, dk, chunk):
    """The rule's call as it stood in ``_gdn_mixer`` before
    ``ops/delta_rule.gated_delta_rule_qkv``: XLA's l2 norms of q and k,
    v's slice, three arrays handed over."""
    from ray_tpu.ops.delta_rule import gated_delta_rule, l2norm_heads
    (B, T, _), H, kw = qkv.shape, g.shape[-1], heads * dk
    q = l2norm_heads(qkv[..., :kw], heads) * dk ** -0.5
    k = l2norm_heads(qkv[..., kw:2 * kw], heads)
    v = qkv[..., 2 * kw:].reshape(B, T, H, -1)
    return gated_delta_rule(q.astype(qkv.dtype).reshape(B, T, heads, dk),
                            k.astype(qkv.dtype).reshape(B, T, heads, dk),
                            v, g, beta, chunk=chunk)


@pytest.mark.parametrize("form", ["the_xla_form", "the_kernels"])
def test_the_mixer_with_the_rules_entry_is_the_norms_and_slices(monkeypatch,
                                                                form):
    """``_gdn_mixer`` under the DeltaNet block's checkpoint and
    ``jax.grad`` with ``gated_delta_rule_qkv`` beside the same mixer with
    the norms and slices written out.  As the CPU takes the entry
    (float32, 8-wide heads: the XLA form, unchanged) the two are the same
    bits, loss and every gradient.  As the cell takes it (bf16, 2 key and
    4 value heads of 128, two chunks of 64; the kernels' interpret mode
    here) the solve runs ONCE (its ``T`` is what the checkpoint keeps),
    the kernel that follows twice (the pass and its recomputation), each
    backward once, and everything stays the written-out lines' to bf16's
    rounding."""
    from ray_tpu.ops import delta_rule
    kernels = form == "the_kernels"
    cfg = dataclasses.replace(CFG, gdn_key_dim=128, gdn_value_dim=128,
                              rule_chunk=64, dtype=jnp.bfloat16) \
        if kernels else CFG
    lp = _layer(random_tree(cfg, seed=9))
    u = jax.random.normal(jax.random.key(16), (2, 128, cfg.n_embd)) \
        .astype(cfg.dtype)
    probe = jax.random.normal(jax.random.key(17), u.shape)
    kept = jax.checkpoint_policies.save_only_these_names(delta_rule.INVERSE)

    def loss(u, lp):
        out = jax.checkpoint(lambda u, lp: qn._gdn_mixer(u, lp, cfg)[0],
                             policy=kept)(u, lp)
        return (out.astype(jnp.float32) * probe).sum()

    run = jax.value_and_grad(loss, argnums=(0, 1))
    if kernels:
        monkeypatch.setattr(delta_rule, "STEP_CHUNKS", 1)
        monkeypatch.setattr(
            delta_rule, "gated_delta_rule_qkv",
            lambda qkv, g, beta, heads, dk, chunk:
            delta_rule.delta_rule_chunks_qkv(
                qkv, g, beta, jnp.zeros((2, 4, 128, 128)), heads, dk,
                chunk=chunk, interpret=True))
        program = str(jax.make_jaxpr(run)(u, lp))
        calls = re.findall(r"jit\[\s*name=(delta_rule_\w+)", program)
        assert sorted(calls) == [
            "delta_rule_bwd", "delta_rule_fwd", "delta_rule_fwd",
            "delta_rule_solve", "delta_rule_solve_bwd"]
    got, got_grads = run(u, lp)
    monkeypatch.setattr(delta_rule, "gated_delta_rule_qkv", _norms_and_slices)
    want, want_grads = run(u, lp)
    got_grads, want_grads = _flat(got_grads), _flat(want_grads)
    assert np.abs(np.float32(want_grads[
        next(key for key in want_grads if "in_proj_qkvz" in key)])).max() > 0
    if not kernels:
        assert float(got) == float(want)
        for key, b in want_grads.items():
            np.testing.assert_array_equal(np.float32(got_grads[key]),
                                          np.float32(b), err_msg=key)
        return
    assert abs(float(got) - float(want)) < 0.02 * abs(float(want)) + 0.05
    for key, b in want_grads.items():
        a, b = np.float32(got_grads[key]), np.float32(b)
        if np.abs(b).max() == 0:
            continue                    # the mixer reads none of the MoE's
        np.testing.assert_allclose(a, b, atol=0.03 * np.abs(b).max(),
                                   err_msg=key)


# ------------------------------------------ the gated output norm's entry
def _four_lines(o, z, scale, eps):
    """The mixer's gated output norm as it stood in ``_gdn_mixer`` before
    ``ops/ssm.gated_rms_norm``, with the rounding ``gdn_out`` carried."""
    dtype = o.dtype
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)
    o = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
    return o.astype(dtype)


@pytest.mark.parametrize("form", ["the_xla_form", "the_kernels"])
def test_the_mixer_with_the_norms_entry_is_the_four_lines(monkeypatch, form):
    """``_gdn_mixer`` under ``jax.checkpoint`` and ``jax.grad`` with
    ``ops/ssm.gated_rms_norm`` beside the same mixer with the four lines
    written out.  As the CPU takes the entry (float32, 12-wide heads) the
    two are the same bits, value and every gradient.  As the cell takes it
    (bf16, 4 value heads of 128, two time blocks; the kernels' interpret
    mode here) the forward kernel runs twice (the pass and its
    recomputation) and the backward once, and everything stays the four
    lines' to bf16's rounding, the scale's gradient among them."""
    from ray_tpu.ops import ssm
    kernels = form == "the_kernels"
    cfg = dataclasses.replace(CFG, gdn_value_dim=128, dtype=jnp.bfloat16) \
        if kernels else CFG
    lp = _layer(random_tree(cfg, seed=8))
    u = jax.random.normal(jax.random.key(14), (2, 64, cfg.n_embd)) \
        .astype(cfg.dtype)
    probe = jax.random.normal(jax.random.key(15), u.shape)

    def loss(u, lp):
        out = jax.checkpoint(lambda u, lp: qn._gdn_mixer(u, lp, cfg)[0])(u, lp)
        return (out.astype(jnp.float32) * probe).sum()

    run = jax.value_and_grad(loss, argnums=(0, 1))
    if kernels:
        monkeypatch.setattr(ssm, "NORM_ROWS", 32)
        monkeypatch.setattr(ssm, "NORM_STEP", 16)
        monkeypatch.setattr(
            ssm, "gated_rms_norm", lambda o, z, s, eps:
            ssm._gated_norm_kernels(o.reshape(z.shape), z, s, eps, True)
            .reshape(o.shape))
        program = str(jax.make_jaxpr(run)(u, lp))
        calls = re.findall(r"jit\[\s*name=(gated_norm_\w+)", program)
        assert sorted(calls) == ["gated_norm_bwd"] + ["gated_norm_fwd"] * 2
    got, got_grads = run(u, lp)
    monkeypatch.setattr(ssm, "gated_rms_norm", _four_lines)
    want, want_grads = run(u, lp)
    got_grads, want_grads = _flat(got_grads), _flat(want_grads)
    assert np.abs(np.float32(want_grads[
        next(key for key in want_grads if "out_norm" in key)])).max() > 0
    if not kernels:
        assert float(got) == float(want)
        for key, b in want_grads.items():
            np.testing.assert_array_equal(np.float32(got_grads[key]),
                                          np.float32(b), err_msg=key)
        return
    assert abs(float(got) - float(want)) < 0.02 * abs(float(want)) + 0.05
    for key, b in want_grads.items():
        a, b = np.float32(got_grads[key]), np.float32(b)
        if np.abs(b).max() == 0:
            continue                    # the mixer reads none of the MoE's
        np.testing.assert_allclose(a, b, atol=0.03 * np.abs(b).max(),
                                   err_msg=key)
