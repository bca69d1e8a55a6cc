"""The plain float32 OLMoE against the program's model (models/llama.py
with experts and QK-norm) at a tiny size on the CPU: logits, the loss and
its two router terms, the gradient of every parameter group, dropless
routing under a forced imbalance, prefill -> decode through the paged
cache; that the reference would catch a wrong QK-norm axis or RoPE
convention; the family file, the operation count and the new reducer."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops_olmoe, manifest
from perfbench.reducers import expert_matmul_peak_share
from perfbench.reference import olmoe_ref
from ray_tpu.models import llama

# hidden 64, 2 layers, 4 heads x 16, 8 experts of width 32, 2 a token
CFG = dataclasses.replace(llama.tiny_moe(vocab=211, seq=48),
                          dtype=jnp.float32, remat=False)
SETTINGS = {"num_attention_heads": CFG.n_head,
            "num_key_value_heads": CFG.n_kv_head,
            "num_experts_per_tok": CFG.experts_per_token,
            "rms_norm_eps": CFG.rms_eps, "rope_theta": CFG.rope_theta,
            "router_aux_loss_coef": CFG.router_aux_coef,
            "router_z_loss_coef": CFG.router_z_coef}
# both sides compute in float32 here, so they differ by the order of sums
# only (the program sorts rows by expert, the reference applies every
# expert to every token); 2e-4 is ~100 float32 roundings of O(1) logits,
# and a wrong mask, position, norm axis or expert moves logits by ~0.1
ATOL = 2e-4
CONFIG_FILE = manifest.BENCH_DIR / "configs" / "olmoe-1b-7b.json"


def _random_scales(params, rng):
    """Norm scales away from 1, so that a scale applied on the wrong axis
    or left out shows."""
    def leaf(path, a):
        if "scale" in jax.tree_util.keystr(path):
            return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def params():
    return _random_scales(llama.init_params(jax.random.key(3), CFG),
                          np.random.default_rng(5))


@pytest.fixture(scope="module")
def batch():
    toks = np.random.default_rng(1).integers(0, CFG.vocab_size, (3, 41))
    return {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32)}


@pytest.fixture(scope="module")
def grads(params, batch):
    got = jax.grad(llama.loss_fn)(params, batch, CFG)
    want = jax.grad(olmoe_ref.loss)(params, batch["inputs"],
                                    batch["targets"], SETTINGS)
    flat = lambda t: {jax.tree_util.keystr(p): np.asarray(a)  # noqa: E731
                      for p, a in jax.tree_util.tree_leaves_with_path(t)}
    return flat(got), flat(want)


def test_logits_equal_the_programs_forward(params, batch):
    want = np.asarray(olmoe_ref.logits(params, batch["inputs"], SETTINGS))
    got = np.asarray(llama.forward(params, batch["inputs"], CFG))
    assert np.abs(got - want).max() < ATOL
    assert np.abs(want).max() > 0.1             # not all-zero logits


def test_loss_equals_loss_fn(params, batch):
    want = float(olmoe_ref.loss(params, batch["inputs"], batch["targets"],
                                SETTINGS))
    got = float(llama.loss_fn(params, batch, CFG))
    assert got == pytest.approx(want, abs=ATOL)
    assert abs(got - np.log(CFG.vocab_size)) < 0.5


def test_the_tolerance_fails_a_bf16_forward(params, batch):
    low = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    got = np.asarray(llama.forward(params, batch["inputs"], low))
    want = np.asarray(olmoe_ref.logits(params, batch["inputs"], SETTINGS))
    assert np.abs(got - want).max() > ATOL


@pytest.mark.parametrize("term", ["balance_loss", "z_loss"])
def test_router_terms_alone(params, batch, term):
    """L_balance and L_z, each a mean over the layers, and both well away
    from the values a uniform router gives (1 and ln(8)^2)."""
    _, stats = llama.forward_hidden(params, batch["inputs"], CFG)
    _, balance, z = olmoe_ref.loss_terms(params, batch["inputs"],
                                         batch["targets"], SETTINGS)
    want = {"balance_loss": balance, "z_loss": z}[term]
    got = getattr(stats, term)
    assert got.shape == (CFG.n_layer,)
    assert float(got.mean()) == pytest.approx(float(want), abs=1e-5)


def test_router_terms_enter_the_loss_at_their_coefficients(params, batch):
    ce, balance, z = (float(x) for x in olmoe_ref.loss_terms(
        params, batch["inputs"], batch["targets"], SETTINGS))
    for aux, zc in ((0.0, 0.0), (0.5, 0.0), (0.0, 0.25)):
        cfg = dataclasses.replace(CFG, router_aux_coef=aux, router_z_coef=zc)
        assert float(llama.loss_fn(params, batch, cfg)) == pytest.approx(
            ce + aux * balance + zc * z, abs=ATOL)


GROUPS = ["wte", "lm_head", "norm_f", "attn_norm", "wq", "wk", "wv", "wo",
          "q_norm", "k_norm", "mlp_norm", "router", "w_gate", "w_up",
          "w_down"]


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_equal_the_references(grads, group):
    got, want = grads
    keys = [k for k in want if f"'{group}'" in k]
    assert keys, (group, sorted(want))
    for key in keys:
        scale = np.abs(want[key]).max()
        assert scale > 0, key                   # the group has a gradient
        assert np.abs(got[key] - want[key]).max() < 1e-4 * scale + 1e-7, key


def test_every_parameter_group_is_compared(grads):
    got, want = grads
    assert set(got) == set(want)
    for key in want:
        assert any(f"'{g}'" in key for g in GROUPS), key


@pytest.mark.parametrize("router", ["all_to_the_same_two", "sharpened"])
def test_dropless_under_forced_imbalance(params, batch, router):
    """A router that sends every token to the same two experts (a zero
    kernel: every probability ties and top-k takes the first two), and one
    sharpened fifty-fold into a hard, uneven routing: GShard's capacity
    would drop most of those tokens; here every one is computed."""
    kernel = np.asarray(params["blocks"]["router"]["kernel"])
    kernel = kernel * (50.0 if router == "sharpened" else 0.0)
    forced = dict(params, blocks={**params["blocks"],
                                  "router": {"kernel": jnp.asarray(kernel)}})
    want = np.asarray(olmoe_ref.logits(forced, batch["inputs"], SETTINGS))
    got = np.asarray(llama.forward(forced, batch["inputs"], CFG))
    assert np.abs(got - want).max() < ATOL
    _, stats = llama.forward_hidden(forced, batch["inputs"], CFG)
    load = np.asarray(stats.load_max_over_mean)
    if router == "all_to_the_same_two":
        # two experts hold every assignment: E / k times the mean
        assert np.allclose(load, CFG.n_experts / CFG.experts_per_token)
    else:
        assert (load > 1.25).all()
    # and taking the experts away changes the logits: they were computed
    zeroed = dict(forced, blocks={**forced["blocks"], "experts": {
        k: jnp.zeros_like(v) for k, v in forced["blocks"]["experts"].items()}})
    off = np.asarray(llama.forward(zeroed, batch["inputs"], CFG))
    assert np.abs(off - got).max() > 20 * ATOL


@pytest.mark.parametrize("variant", [{"qk_norm": "head"},
                                     {"rope": "interleaved"}])
def test_a_wrong_convention_would_be_caught(params, batch, variant):
    """The reference with the QK-norm over each head, or with interleaved
    RoPE pairs, is far outside the tolerance of the program's logits."""
    got = np.asarray(llama.forward(params, batch["inputs"], CFG))
    wrong = np.asarray(olmoe_ref.logits(params, batch["inputs"], SETTINGS,
                                        **variant))
    assert np.abs(got - wrong).max() > 20 * ATOL


def test_prefill_then_decode_through_the_paged_cache(params):
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    n, k, bs = 21, 4, 8
    prompt = np.random.default_rng(4).integers(0, CFG.vocab_size, n).tolist()
    cache = PagedKVCache(16, CFG.n_layer, bs, CFG.n_kv_head, CFG.head_dim,
                         dtype=np.float32)
    try:
        cache.alloc_seq("s", n)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = prompt
        logits, ks, vs = llama.forward_prefill(
            params, jnp.asarray(padded), CFG, last_pos=jnp.int32(n - 1))
        cache.scatter_prefill("s", np.asarray(ks, np.float32)[:, 0],
                              np.asarray(vs, np.float32)[:, 0], n)
        got, seq = [np.asarray(logits)[0]], list(prompt)
        for _ in range(k):
            seq.append(int(np.argmax(got[-1])))
            blk, off, _ = cache.append_slot("s")
            table = cache.table("s")
            tables = np.zeros((1, 6), np.int32)
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, nk, nv = llama.forward_decode(
                params, np.asarray([seq[-1]], np.int32), at, cache.pool,
                tables, at, CFG)
            cache.write_token(blk, off, np.asarray(nk[:, 0], np.float32),
                              np.asarray(nv[:, 0], np.float32))
            got.append(np.asarray(lg)[0])
        ref = np.asarray(olmoe_ref.logits(params, [seq], SETTINGS))[0]
        for i, g in enumerate(got):
            assert np.abs(g - ref[n - 1 + i]).max() < ATOL, i
    finally:
        cache.close()


# ------------------------------------------------- family, count, reducer
def _config():
    return json.loads(CONFIG_FILE.read_text())


def test_the_configuration_keeps_every_published_width():
    config, fam = _config(), manifest.family("olmoe")
    cfg = fam.model_config(config, config["train"]["model_options"])
    assert (cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == \
        (2048, 16, 16, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.ffn_dim) == (64, 8, 1024)
    assert (cfg.vocab_size, cfg.max_positions) == (50304, 4096)
    assert cfg.n_layer == config["num_hidden_layers"] == 3
    assert config["reduced"] == ["num_hidden_layers"]
    assert cfg.qk_norm and cfg.param_dtype == jnp.bfloat16
    assert (cfg.router_aux_coef, cfg.router_z_coef) == (0.01, 0.001)
    # a layer and the state are what the file's cut says they are
    shapes = jax.eval_shape(lambda r: llama.init_params(r, cfg),
                            jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 1464.8


def test_the_rehearsals_gpt2_named_overrides_shrink_it():
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    config, fam = {**_config(), **over["config"]}, manifest.family("olmoe")
    opts = {**config["train"]["model_options"], **over["train_model_options"]}
    cfg = fam.model_config(config, opts)
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.n_kv_head) == (64, 2, 4, 4)
    assert (cfg.vocab_size, cfg.max_positions, cfg.ffn_dim) == (256, 64, 32)
    assert (cfg.n_experts, cfg.experts_per_token) == (64, 8)    # kept
    assert cfg.remat_policy == "full" and cfg.dtype == jnp.float32
    sizes = fam.sizes(config)
    assert sizes["hidden_size"] == 64 and sizes["num_experts"] == 64


@pytest.mark.parametrize("which", ["published", "tiny"])
def test_flops_olmoe_equals_the_count_from_parameter_shapes(which):
    """6 x the matrix parameters a token touches: every 2-D-or-more leaf
    but the embedding, an expert leaf counted for k of its experts."""
    if which == "published":
        config, fam = _config(), manifest.family("olmoe")
        sizes = fam.sizes(config)
        cfg = fam.model_config(config, config["train"]["model_options"])
    else:
        cfg = CFG
        sizes = {"hidden_size": cfg.n_embd, "num_hidden_layers": cfg.n_layer,
                 "num_attention_heads": cfg.n_head,
                 "num_key_value_heads": cfg.n_kv_head,
                 "num_experts": cfg.n_experts,
                 "num_experts_per_tok": cfg.experts_per_token,
                 "intermediate_size": cfg.ffn_dim,
                 "vocab_size": cfg.vocab_size}
    shapes = jax.eval_shape(lambda r: llama.init_params(r, cfg),
                            jax.random.key(0))
    touched = experts = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        key = jax.tree_util.keystr(path)
        if "wte" in key or "scale" in key:
            continue
        if "experts" in key:
            n = leaf.size // cfg.n_experts * cfg.experts_per_token
            experts += n
        else:
            n = leaf.size
        touched += n
    seq = 4096
    assert flops_olmoe.flops_per_token(sizes, seq) == \
        6 * touched + 12 * cfg.n_layer * cfg.n_embd * seq
    assert flops_olmoe.expert_flops_per_token(sizes) == 6 * experts
    if which == "published":
        assert round(flops_olmoe.flops_per_token(sizes, seq) / 1e9, 2) == 2.13
        assert round(flops_olmoe.expert_flops_per_token(sizes) / 1e9, 2) == 0.91


def test_expert_matmul_metrics_read_the_experts_kernels_and_no_other():
    spec = manifest.metric_spec("per_layer", "moe.expert_matmul_peak_share")
    ms_spec = manifest.metric_spec("per_layer", "moe.expert_matmul_ms")
    assert {k: spec["params"][k] for k in ("names", "shapes")} == \
        ms_spec["params"]
    # names as the v5e gave them (my chip run, PR 27).  A 1 s window of
    # which the traced 0.5 s hold 0.1 s of the experts' kernels, over 4
    # steps of 8,192 tokens: 50 ms a step
    traced = {"window": [10.0, 10.5], "host": [], "device": {"/device:TPU:0": [
        ["tpu_custom_call.113 bf16[64,1024,2048]", 10.0, 0.03],
        ["tpu_custom_call.107 bf16[65536,2048]", 10.03, 0.03],
        ["%while.2", 10.0, 0.5],
        ["gmm.20 bf16[65536,1024]", 10.1, 0.02],
        ["tpu_custom_call.115 bf16[64,2048,1024]", 10.12, 0.02],
        ["tpu_custom_call.112 bf16[32,4096,128]", 10.2, 0.1],    # flash
        ["fusion.520 bf16[65536,2048]", 10.3, 0.1]]}}            # a gather
    facts = {"trace": traced, "steps": 4, "window_s": 1.0, "chips": 1,
             "tokens": 4 * 8192, "peak_flops_per_s": 197e12}
    ms = manifest.reducer(ms_spec["reducer"])(facts, ms_spec["params"])
    assert ms == pytest.approx(50.0)
    share = expert_matmul_peak_share.reduce(facts, spec["params"])
    flops = flops_olmoe.expert_flops_per_token(
        manifest.family("olmoe").sizes(_config())) * 8192
    assert share == pytest.approx(100 * flops / 0.05 / 197e12)
    assert 0 < share < 100
    # nothing to read: no trace, a CPU rehearsal without a peak, or a
    # program without such kernels
    none = {**traced, "device": {"/device:TPU:0": [["fusion.7", 10.2, 0.2]]}}
    for lacking in ({"trace": None}, {"peak_flops_per_s": None},
                    {"trace": none}):
        assert expert_matmul_peak_share.reduce({**facts, **lacking},
                                               spec["params"]) is None
    assert manifest.reducer(ms_spec["reducer"])(
        {**facts, "trace": none}, ms_spec["params"]) is None
