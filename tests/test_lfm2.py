"""LFM2-MoE at ``lfm2:tiny`` on the CPU: the gated short convolution
(``ops/short_conv.py``), the model against the plain float32 reference
(``perfbench/reference/lfm2_ref.py``), and its two kinds of per-sequence
state through the serving engine: K/V in the attention layers' pool, conv
tails in the conv layers' store, in one cache manager, and the hand-over of
the chosen experts.

Tolerances.  The engine tests run the model in float32 (the preset's
``dtype`` patched), where program and reference differ by summation order
only: 1e-5 of logits whose standard deviation is 1.  ``TIGHT`` = 2e-4 is
twenty times that; the same forward with bf16 activations differs by 0.02
or more (shown below), a conv tail from padding, a neighbour's row, a
wrong pool layer or one expert taken the other way each by 1e-2 or more.
In bf16 the tiny model's nine layers (64 wide, heads of 8: each norm and
each router averages over few numbers) differ from the reference under the
program's own choice of experts by 0.23-0.38 at the worst of 10,240 logits
(six seeds, 2 x 40 tokens; 2.7-4.8% of the decisions differ from the
reference's own); ``BF16`` = 1.15 is three times that, as the cells'
``logit_atol`` are set, and the same reference with every matrix rounded to
float8_e4m3 differs by 2.18-3.35: 1.9 times the limit or more, 5.6 times
the sound runs' largest.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.families import lfm2 as family
from perfbench.reference import lfm2_ref
from ray_tpu.models import lfm2
from ray_tpu.ops import short_conv
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from test_falcon_h1 import _recorded

TIGHT, BF16 = 2e-4, 1.15


def _f32(cfg):
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _sizes(cfg):
    """The reference's settings (config.json names) of a program config."""
    return family.sizes_of_model(cfg)


@pytest.fixture
def f32_tiny(monkeypatch):
    """``lfm2:tiny`` resolves to the tiny model in float32."""
    cfg = _f32(lfm2.tiny())
    monkeypatch.setitem(lfm2.PRESETS, "tiny", lambda: cfg)
    return cfg


@pytest.fixture(scope="module")
def tiny_params():
    return lfm2.init_params(jax.random.key(1), lfm2.tiny())


def engine_cfg(**kw):
    base = dict(model="lfm2:tiny", num_blocks=64, block_size=8,
                max_num_seqs=4, max_model_len=64, max_prefill_tokens=32,
                prefill_len_buckets=(16, 32, 64),
                decode_batch_buckets=(1, 2, 4), share_weights=False)
    base.update(kw)
    return EngineConfig(**base)


# ------------------------------------------------------------------ the op
def _direct(p, w):
    """The gated conv by its definition, a position at a time: p (T, 3 E),
    w (K, E) -> (g (T, E), z (T, E))."""
    b, c, x = np.split(np.asarray(p, np.float64), 3, axis=-1)
    z = b * x
    k_w = w.shape[0]
    out = np.zeros_like(z)
    for t in range(z.shape[0]):
        for j in range(k_w):
            at = t - (k_w - 1) + j
            if at >= 0:
                out[t] += np.asarray(w, np.float64)[j] * z[at]
    return c * out, z


@pytest.mark.parametrize("last", [0, 1, 2, 9, 14])
def test_gated_conv_is_the_direct_sum_whole_and_step_by_step(last):
    """The whole sequence against the definition; the tail at ``last`` is
    the last two real z (zeros before the sequence), whatever the padding
    behind it holds; the step from that tail gives position last + 1."""
    keys = jax.random.split(jax.random.key(last), 2)
    p = jax.random.normal(keys[0], (2, 16, 3 * 6))
    w = jax.random.normal(keys[1], (3, 6))
    g, tail = short_conv.gated_conv(p, w, jnp.int32(last))
    assert g.dtype == tail.dtype == jnp.float32 and tail.shape == (2, 2, 6)
    for i in range(2):
        want, z = _direct(p[i], w)
        np.testing.assert_allclose(g[i], want, atol=1e-5)
        real = np.zeros((2, 6))
        have = z[max(0, last - 1):last + 1]
        real[2 - have.shape[0]:] = have
        np.testing.assert_allclose(tail[i], real, atol=1e-6)
    # padding behind last_pos is poison: the tail does not see it
    poisoned = p.at[:, last + 1:].set(1e3)
    _, same = short_conv.gated_conv(poisoned, w, jnp.int32(last))
    np.testing.assert_array_equal(same, tail)
    # token by token from a zero tail: every position, and the tails agree
    step_tail = jnp.zeros((2, 2, 6))
    for t in range(last + 2 if last + 1 < 16 else last + 1):
        if t == last + 1:
            np.testing.assert_allclose(step_tail, tail, atol=1e-6)
        g_t, step_tail = short_conv.gated_conv_step(step_tail, p[:, t], w)
        np.testing.assert_allclose(g_t, g[:, t], atol=1e-5)


def test_the_conv_without_a_bias_is_the_conv_with_a_zero_one():
    from ray_tpu.ops import ssm
    keys = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(keys[0], (2, 9, 5))
    w = jax.random.normal(keys[1], (3, 5))
    y, tail = ssm.causal_conv(x, w, None, jnp.int32(4))
    y0, tail0 = ssm.causal_conv(x, w, jnp.zeros(5), jnp.int32(4))
    np.testing.assert_array_equal(y, y0)
    np.testing.assert_array_equal(tail, tail0)
    s, t = ssm.conv_step(tail, x[:, 5], w, None)
    s0, t0 = ssm.conv_step(tail0, x[:, 5], w, jnp.zeros(5))
    np.testing.assert_array_equal(s, s0)
    np.testing.assert_array_equal(t, t0)


# --------------------------------------------------------------- the model
def test_the_presets_are_layers_of_four_kinds():
    big, tiny = lfm2.PRESETS["lfm2-24b-a2b-l9"](), lfm2.tiny()
    for cfg in (big, tiny):
        assert cfg.n_layer == 9 and cfg.n_dense_layer == 1
        assert cfg.layer_types[0] == lfm2.CONV
        assert cfg.period == (lfm2.ATTN, lfm2.CONV, lfm2.CONV, lfm2.CONV)
        assert cfg.n_period == 2
        assert lfm2.cache_layers(cfg) == {"kv": 2, "state": 7}
        assert lfm2.routed_layers(cfg) == {
            "layers": 8, "k": cfg.experts_per_token}
    assert (big.n_embd, big.n_head, big.n_kv_head, big.head_dim) \
        == (2048, 32, 8, 64)
    assert (big.n_experts, big.experts_per_token, big.expert_dim,
            big.ffn_dim, big.vocab_size) == (64, 4, 1536, 11776, 65536)
    assert big.dtype == big.param_dtype == jnp.bfloat16
    params = jax.eval_shape(lambda k: lfm2.init_params(k, big),
                            jax.random.key(0))
    assert lfm2.param_count(params) == 5_177_950_976
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        wide = any(getattr(k, "key", None) in lfm2.WIDE_PARAMS for k in path)
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16), path
    # the published 40: nine whole periods and half of one behind them
    whole = lfm2.Lfm2Config()
    assert whole.period == big.period and whole.n_period == 9
    assert whole.tail_types == (lfm2.ATTN, lfm2.CONV)
    assert lfm2.cache_layers(whole) == {"kv": 10, "state": 30}
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config(n_layer=3)


def test_layers_behind_the_last_whole_period_are_the_references_too():
    """1 dense + a period + half of one: the tail's K/V, conv tails and
    choices come in layer order behind the scan's, in prefill and in a
    decode step."""
    from ray_tpu.serve.llm.kv_cache import device_shape, write_rows
    cfg = dataclasses.replace(
        _f32(lfm2.tiny()), n_layer=7,
        layer_types=(lfm2.CONV, lfm2.ATTN, lfm2.CONV, lfm2.CONV, lfm2.CONV,
                     lfm2.ATTN, lfm2.CONV))
    assert cfg.n_period == 1 and cfg.tail_types == (lfm2.ATTN, lfm2.CONV)
    params = lfm2.init_params(jax.random.key(7), cfg)
    tokens = jax.random.randint(jax.random.key(8), (1, 12), 0, cfg.vocab_size)
    want = np.asarray(lfm2_ref.logits(params, tokens, _sizes(cfg)))
    n = 11
    logits, ks, vs, state, ids = lfm2.forward_prefill(
        params, tokens[:, :n], cfg, jnp.int32(n - 1), choices=True)
    assert ks.shape[0] == 2 and state["conv"].shape[0] == 5
    assert ids.shape == (6, n, 2)
    np.testing.assert_allclose(logits, want[:, n - 1], atol=TIGHT)
    # one decode step over a pool and a store filled from that prefill
    pool = jnp.zeros(device_shape(4, 2, 8, cfg.n_kv_head, cfg.head_dim))
    t = jnp.arange(n)
    pool = write_rows(pool, t // 8, t % 8, ks[:, 0], vs[:, 0])
    store = {"conv": jnp.zeros((5, 2, 2, cfg.n_embd)).at[:, 1].set(
        state["conv"][:, 0])}
    step, k, v, store, chose = lfm2.forward_decode(
        params, tokens[:, n], jnp.asarray([n]), pool,
        jnp.asarray([[0, 1, 0, 0]]), jnp.asarray([n]), cfg, state=store,
        rows=jnp.asarray([1]), choices=True)
    np.testing.assert_allclose(step, want[:, n], atol=TIGHT)
    assert k.shape == (2, 1, cfg.n_kv_head, cfg.head_dim)
    assert chose.shape == (6, 1, 2)
    assert not np.asarray(store["conv"][:, 0]).any()    # row 0 is nobody's


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, TIGHT),
                                        (jnp.bfloat16, BF16)])
def test_forward_agrees_with_the_plain_reference(tiny_params, dtype, atol):
    """Logits, not tokens.  In float32 the two differ by the order of
    sums (1e-5); the tolerance is twenty times that, and bf16 activations
    in float32's place fail it by a hundred times (the substitution the
    tolerance has to catch).  In bf16 the reference is computed under the
    program's own choice of experts, as the serving check computes it."""
    cfg = dataclasses.replace(lfm2.tiny(), dtype=dtype)
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab_size)
    got, _, _, _, ids = lfm2.forward_prefill(tiny_params, tokens, cfg,
                                             choices=True)
    want, audit = lfm2_ref.logits(tiny_params, tokens, _sizes(cfg),
                                  choices=np.asarray(ids))
    assert 0.5 < float(np.std(want)) < 2.0      # logits a check can fail on
    np.testing.assert_allclose(got, want, atol=atol)
    if dtype == jnp.float32:
        assert audit["differing"] == 0 and audit["worst_margin"] == 0.0
        np.testing.assert_allclose(
            want, lfm2_ref.logits(tiny_params, tokens, _sizes(cfg)), atol=0)
        low = lfm2.forward(tiny_params, tokens,
                           dataclasses.replace(cfg, dtype=jnp.bfloat16))
        assert np.abs(low - want).max() > 100 * TIGHT


def test_float8_weights_fail_the_bf16_tolerance(tiny_params):
    """The precision below the one served: every matrix of the reference
    rounded to float8_e4m3 is outside the tolerance bf16 is held to."""
    cfg = lfm2.tiny()
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab_size)
    got, _, _, _, ids = lfm2.forward_prefill(tiny_params, tokens, cfg,
                                             choices=True)
    low = jax.tree_util.tree_map_with_path(
        lambda path, w: w if any(getattr(k, "key", None) in lfm2.WIDE_PARAMS
                                 for k in path)
        else w.astype(jnp.float8_e4m3fn).astype(jnp.float32), tiny_params)
    ref8, _ = lfm2_ref.logits(low, tokens, _sizes(cfg),
                              choices=np.asarray(ids))
    assert np.abs(np.asarray(got) - ref8).max() > 1.5 * BF16


def test_choices_are_the_references_own_top_k_where_no_tie_is_near(
        tiny_params):
    """The hand-over: (routed layers, rows, k) int32 in layer order, and in
    float32 every decision is the reference's own set."""
    cfg = _f32(lfm2.tiny())
    tokens = jax.random.randint(jax.random.key(4), (1, 33), 0, cfg.vocab_size)
    *_, ids = lfm2.forward_prefill(tiny_params, tokens, cfg, choices=True)
    assert ids.shape == (8, 33, 2) and ids.dtype == jnp.int32
    assert int(ids.min()) >= 0 and int(ids.max()) < cfg.n_experts
    _, audit = lfm2_ref.logits(tiny_params, tokens, _sizes(cfg),
                               choices=np.asarray(ids))
    assert audit == {"decisions": 8 * 33, "differing": 0,
                     "worst_margin": 0.0}
    # a far expert in one layer at one position is seen, and moves logits
    far = np.asarray(ids).copy()
    far[3, 7, 1] = (far[3, 7, 0] + 1 + (far[3, 7, 1] == far[3, 7, 0] + 1)) \
        % cfg.n_experts
    base = lfm2_ref.logits(tiny_params, tokens, _sizes(cfg))
    moved, audit = lfm2_ref.logits(tiny_params, tokens, _sizes(cfg),
                                   choices=far)
    # (the layers behind it see another residual, and may differ too)
    assert audit["differing"] >= 1 and audit["worst_margin"] > 0
    assert np.abs(np.asarray(moved) - base).max() > 50 * TIGHT
    # without the keyword the forwards return what they always did
    assert len(lfm2.forward_prefill(tiny_params, tokens, cfg)) == 4


@pytest.mark.parametrize("n,bucket", [(13, 16), (16, 16), (21, 32)])
def test_prefill_in_a_bucket_is_the_unpadded_prompt(tiny_params, n, bucket):
    """Logits, K/V and the conv tails at ``last_pos`` of a padded prompt
    are those of the prompt alone: K/V from the 2 attention layers, tails
    from the 7 conv layers."""
    cfg = _f32(lfm2.tiny())
    prompt = jax.random.randint(jax.random.key(n), (1, n), 0, cfg.vocab_size)
    padded = jnp.pad(prompt, ((0, 0), (0, bucket - n)), constant_values=7)
    last = jnp.int32(n - 1)
    logits, ks, vs, state = lfm2.forward_prefill(tiny_params, padded, cfg,
                                                 last)
    want, wk, wv, wstate = lfm2.forward_prefill(tiny_params, prompt, cfg,
                                                last)
    np.testing.assert_allclose(logits, want, atol=TIGHT)
    assert ks.shape == vs.shape == (2, 1, bucket, cfg.n_kv_head,
                                    cfg.head_dim)
    np.testing.assert_allclose(ks[:, :, :n], wk, atol=TIGHT)
    np.testing.assert_allclose(vs[:, :, :n], wv, atol=TIGHT)
    spec = lfm2.recurrent_state(cfg)["conv"]
    assert state["conv"].shape == (7, 1) + spec.shape == (7, 1, 2, 64)
    assert state["conv"].dtype == spec.dtype == jnp.float32
    np.testing.assert_allclose(state["conv"], wstate["conv"], atol=TIGHT)
    ref = lfm2_ref.logits(tiny_params, prompt, _sizes(cfg))
    np.testing.assert_allclose(logits, ref[:, -1], atol=TIGHT)


# ---------------------------------------------------------------- the cache
def test_the_pool_has_the_attention_layers_and_the_store_the_conv_layers(
        f32_tiny):
    eng = LLMEngine(engine_cfg(), start=False)
    try:
        runner, cache = eng.runner, eng.cache
        assert (runner.kv_layers, runner.state_layers) == (2, 7)
        assert (cache.kv_layers, cache.state_layers) == (2, 7)
        held = cache.pool.read(lambda h: h)
        # (L, 2, N, bs, F): 2 x 8 = 16 lanes in use of the tile's 128
        assert held["kv"].shape == (2, 2, 64, 8, 128)
        assert {k: v.shape for k, v in held["state"].items()} \
            == {"conv": (7, 5, 2, 64)}          # 4 slots + staging
        assert cache.block_shape == (2, 2, 8, 2, 8)
        assert cache.state_bytes == 7 * 5 * 2 * 64 * 4
        stats = eng.stats()
        assert stats["kv_layers"] == 2 and stats["state_layers"] == 7
    finally:
        eng.shutdown()


def test_a_family_whose_layers_are_alike_gets_what_it_had():
    """GPT-2 and Falcon-H1 export no ``cache_layers``: pool and store
    count ``n_layer``, the store only where there is state."""
    from ray_tpu.serve.llm.model_runner import ModelRunner
    plain = ModelRunner(engine_cfg(model="gpt2:tiny"))
    assert (plain.kv_layers, plain.state_layers) == (plain.n_layer, 0)
    assert plain.route_spec is None and plain.choices is None
    both = ModelRunner(engine_cfg(model="falcon_h1:tiny"))
    assert (both.kv_layers, both.state_layers) == (both.n_layer,) * 2
    assert both.route_spec is None
    routed = ModelRunner(engine_cfg(model="llama:tiny-moe"))
    assert routed.route_spec == {"layers": 2, "k": 2}
    assert ModelRunner(engine_cfg(model="llama:tiny")).route_spec is None


# --------------------------------------------------------------- the engine
def _assert_logits_are_the_references(eng, cfg, got, streams, atol=TIGHT):
    for stream, prompt, output in streams:
        full = np.asarray([list(prompt) + list(output)], np.int32)
        ref = np.asarray(lfm2_ref.logits(eng.runner.params, full,
                                         _sizes(cfg)))[0]
        assert got[stream.seq_id], stream.seq_id
        for n_ctx, logits in got[stream.seq_id]:
            # logits after n_ctx tokens of the sequence predict token n_ctx
            np.testing.assert_allclose(logits, ref[n_ctx - 1], atol=atol)


def test_engine_loop_prefill_then_decode_is_the_reference(f32_tiny):
    """Two sequences of different lengths in one batch through LLMEngine's
    own loop (a 13-token prompt in the 16 bucket: 3 padded positions, the
    conv tail cut at the last real one; a 27-token prompt in the 32
    bucket): every step's logits are the reference's full forward over
    prompt + output, through the paged pool and the store; the counters
    and the spans' attributes of what this family adds are there."""
    eng = LLMEngine(engine_cfg())
    try:
        got = _recorded(eng)
        prompts = [list(range(3, 16)), list(range(40, 67))]
        streams = [eng.submit(p, SamplingParams(max_tokens=m))
                   for p, m in zip(prompts, (12, 9))]
        outputs = [s.tokens() for s in streams]
        assert [len(o) for o in outputs] == [12, 9]
        _assert_logits_are_the_references(
            eng, f32_tiny, got, list(zip(streams, prompts, outputs)))
        stats = eng.stats()
        assert stats["state_rows"] == 4 and stats["state_rows_used"] == 0
        assert stats["state_commits"] == 2
        assert eng.cache.free_block_count() == 64
        # every committed decode step counted its distinct experts: between
        # k (one live row) and rows x k a routed layer
        steps = stats["routed_layer_steps"] // 8
        assert 0 < steps <= stats["decode_steps"]
        assert 2 * 8 * steps <= stats["experts_touched"] <= 2 * 2 * 8 * steps
        ids = np.asarray(eng.runner.choices)
        assert ids.shape[0] == 8 and ids.shape[2] == 2 \
            and ids.dtype == np.int32
    finally:
        eng.shutdown()


def test_the_span_and_the_histogram_say_what_a_step_touched(
        f32_tiny, monkeypatch):
    """``llm.decode`` (or the ``llm.decode.drain`` that reads a step) is
    told ``experts_touched``, and ``kv_layers`` / ``state_layers``; the
    catalog's histogram observes once a committed step."""
    from ray_tpu.util import metrics, tracing
    before = metrics.registry_snapshot().get(
        "rtpu_llm_moe_experts_touched") or {"series": []}
    seen = sum(s["value"]["count"] for s in before["series"])
    said, real = [], tracing.hot_span.set

    def recorded(self, **attrs):
        said.append((self.name, attrs))
        real(self, **attrs)

    monkeypatch.setattr(tracing.hot_span, "set", recorded)
    eng = LLMEngine(engine_cfg())
    try:
        eng.generate(list(range(5, 20)), SamplingParams(max_tokens=6))
        stats = eng.stats()
    finally:
        eng.shutdown()
    layers = [a for name, a in said
              if name == "llm.decode" and "kv_layers" in a]
    assert layers and all(a["kv_layers"] == 2 and a["state_layers"] == 7
                          for a in layers)
    touched = [a["experts_touched"] for name, a in said
               if name in ("llm.decode", "llm.decode.drain")
               and "experts_touched" in a]
    assert sum(touched) == stats["experts_touched"] > 0
    assert all(t == 2 * 8 for t in touched)      # one live row: k a layer
    after = metrics.registry_snapshot()["rtpu_llm_moe_experts_touched"]
    count = sum(s["value"]["count"] for s in after["series"])
    assert count - seen == len(touched)


def test_rows_padded_up_to_the_bucket_choose_no_expert_of_their_own(
        f32_tiny):
    """One sequence in a decode bucket of 4: the three padded rows are
    handed over with the live row's choice in every routed layer (they
    lie in groups it opened, so the step reads k experts a layer and
    ``experts_touched`` is what it read), and the live row's logits are
    the reference's all the same."""
    eng = LLMEngine(engine_cfg(decode_batch_buckets=(4,)))
    try:
        got = _recorded(eng)
        prompt = list(range(5, 20))
        stream = eng.submit(prompt, SamplingParams(max_tokens=6))
        out = stream.tokens()
        _assert_logits_are_the_references(eng, f32_tiny, got,
                                          [(stream, prompt, out)])
        ids = np.asarray(eng.runner.choices)
        assert ids.shape == (8, 4, 2)
        assert (ids[:, 1:] == ids[:, :1]).all()
        stats = eng.stats()
        assert stats["experts_touched"] == 2 * stats["routed_layer_steps"]
    finally:
        eng.shutdown()


def test_choice_of_live_rows_keeps_the_live_and_copies_the_first():
    from ray_tpu.ops.moe import choice_of_live_rows
    idx = jnp.array([[9, 9], [1, 2], [3, 4], [5, 6]])
    live = jnp.array([False, True, True, False])
    assert choice_of_live_rows(idx, live).tolist() \
        == [[1, 2], [1, 2], [3, 4], [1, 2]]


def test_interleaved_sequences_keep_to_their_own_rows(f32_tiny):
    """Six sequences over four slots, arriving and finishing at different
    steps, so that rows and blocks are handed on and the batch order
    changes: each one's logits are those of its own full forward."""
    eng = LLMEngine(engine_cfg())
    try:
        got = _recorded(eng)
        rng = np.random.default_rng(5)
        jobs = [(rng.integers(1, 120, size=n).tolist(), m)
                for n, m in [(5, 9), (17, 4), (9, 14), (30, 6), (3, 11),
                             (12, 7)]]
        streams = [eng.submit(p, SamplingParams(max_tokens=m))
                   for p, m in jobs]
        outs = [s.tokens() for s in streams]
        assert [len(o) for o in outs] == [m for _, m in jobs]
        _assert_logits_are_the_references(
            eng, f32_tiny, got,
            [(s, p, o) for s, (p, _), o in zip(streams, jobs, outs)])
        stats = eng.stats()
        assert stats["state_rows_used"] == 0 and stats["state_commits"] == 6
        assert eng.cache.free_block_count() == 64
    finally:
        eng.shutdown()


def test_a_preempted_sequence_is_recomputed_to_the_same_tokens(f32_tiny):
    """Cache pressure evicts a sequence (its row goes with its blocks);
    the re-prefill over prompt + output rebuilds K/V and tails, and the
    tokens are those of an engine that never preempts."""
    small = dict(num_blocks=6, block_size=4, max_model_len=32,
                 max_prefill_tokens=16, prefill_len_buckets=(16, 32))
    eng = LLMEngine(engine_cfg(**small))
    big = LLMEngine(engine_cfg(**{**small, "num_blocks": 64}))
    try:
        got = _recorded(eng)
        sp = SamplingParams(max_tokens=12)
        prompts = [[1 + i, 2, 3] for i in range(3)]
        streams = [eng.submit(p, sp) for p in prompts]
        outs = [s.tokens() for s in streams]
        assert eng.stats()["preemptions"] >= 1
        assert outs == [big.generate(p, sp) for p in prompts]
        _assert_logits_are_the_references(
            eng, f32_tiny, got, list(zip(streams, prompts, outs)))
        stats = eng.stats()
        assert stats["state_rows_used"] == 0
        assert stats["state_commits"] == stats["prefill_steps"] > 3
        assert eng.cache.free_block_count() == 6
    finally:
        eng.shutdown(), big.shutdown()


def test_rows_and_blocks_come_back_on_cancel(f32_tiny):
    import time
    eng = LLMEngine(engine_cfg())
    try:
        streams = [eng.submit([5, 6, 7, 8], SamplingParams(max_tokens=50))
                   for _ in range(3)]
        firsts = [next(iter(s)) for s in streams]
        assert len(firsts) == 3 and eng.stats()["state_rows_used"] == 3
        for s in streams:
            s.cancel()
        for _ in range(200):
            if eng.stats()["state_rows_used"] == 0:
                break
            time.sleep(0.02)
        assert eng.stats()["state_rows_used"] == 0
        assert eng.cache.free_block_count() == 64
        assert len(eng.generate([9, 9, 9], SamplingParams(max_tokens=3))) == 3
    finally:
        eng.shutdown()


def test_export_and_import_refuse_this_family(f32_tiny):
    eng = LLMEngine(engine_cfg(), start=False)
    try:
        with pytest.raises(NotImplementedError, match="recurrent state"):
            eng.prefill_remote([1, 2, 3])
        with pytest.raises(NotImplementedError, match="recurrent state"):
            eng.attach({"model": "lfm2:tiny"})
        assert eng.stats()["state_rows_used"] == 0
    finally:
        eng.shutdown()


def test_a_neighbours_row_or_a_wrong_pool_layer_moves_the_logits(
        f32_tiny, monkeypatch):
    """What the two layer counts are for: a decode step that reads the
    tails of another store row, or the other attention layer's K/V, is
    outside the tolerance."""
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    prompt = list(range(10, 31))

    def decode_diff():
        eng = LLMEngine(engine_cfg())
        try:
            got = _recorded(eng)
            stream = eng.submit(prompt, SamplingParams(max_tokens=5))
            output = stream.tokens()
            full = np.asarray([prompt + output], np.int32)
            ref = np.asarray(lfm2_ref.logits(eng.runner.params, full,
                                             _sizes(f32_tiny)))[0]
            return max(float(np.abs(lg - ref[n - 1]).max())
                       for n, lg in got[stream.seq_id][1:])
        finally:
            eng.shutdown()

    assert decode_diff() < TIGHT
    with monkeypatch.context() as m:
        m.setattr(PagedKVCache, "rows_of", lambda self, tables: np.full(
            len(tables), self.state_rows - 1, np.int32))
        assert decode_diff() > 50 * TIGHT
    from ray_tpu.ops import paged_attention
    real = paged_attention.paged_attention_decode
    with monkeypatch.context() as m:
        m.setattr(paged_attention, "paged_attention_decode",
                  lambda q, pool, layer, *rest: real(q, pool, 1 - layer,
                                                     *rest))
        assert decode_diff() > 50 * TIGHT


def test_bf16_engine_stays_within_the_bf16_tolerance():
    """The preset as it is (bf16 activations), compared as the serving
    check compares: each step against the reference under the experts
    that step chose; pool and store are float32."""
    eng = LLMEngine(engine_cfg())
    try:
        runner, cache, cfg = eng.runner, eng.cache, lfm2.tiny()
        prompt = list(range(40, 61))
        n = len(prompt)
        cache.alloc_seq("s", n)
        logits, ks, vs = runner.prefill(prompt)
        chose = [np.asarray(runner.choices)[:, :n]]
        cache.scatter_prefill("s", ks, vs, n)
        got, seq = [logits], list(prompt)
        for _ in range(12):
            seq.append(int(np.argmax(got[-1])))
            cache.append_slot("s")
            tables = np.zeros((1, eng.cfg.max_blocks_per_seq), np.int32)
            table = cache.table("s")
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, _, _ = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                     cache.pool, tables, at)
            chose.append(np.asarray(runner.choices)[:, :1])
            got.append(lg[0])
        ref, audit = lfm2_ref.logits(runner.params, [seq], _sizes(cfg),
                                     choices=np.concatenate(chose, axis=1))
        for i, g in enumerate(got):
            np.testing.assert_allclose(g, np.asarray(ref)[0, n - 1 + i],
                                       atol=BF16)
        assert audit["decisions"] == 8 * len(seq)
        assert audit["differing"] <= 0.15 * audit["decisions"]
        held = cache.pool.read(lambda h: h)
        assert held["kv"].dtype == held["state"]["conv"].dtype == jnp.float32
    finally:
        eng.shutdown()


# ------------------------------------------------- what the families share
# sha256 (first 16 digits) of each program's StableHLO as the parent of the
# PR that added this family lowered it (commit 6b838ac, this jax): the code
# this family shares with the cells the benchmark has (``ops/ssm.py``'s conv
# with an optional bias, ``ops/moe.route_sigmoid``'s divisor as an argument,
# the cache's two layer counts, the runner's hand-over) did not change what
# they run.  Kanana's block passes no ``eps`` and keeps its 1e-20; its
# digest is the step's since PR 43 changed it on purpose (latent attention
# hands over its parts unjoined, W_q and W_kv_b by column group), and both
# training digests are the steps' since PR 45 did (``ops/moe.
# dropless_experts``: the router's weight multiplies the hidden rows and the
# combine is the dispatch transposed; one grouped matmul fewer a layer) and
# since PR 49 did again (the same function: one sort carries the weights
# and gives the order, its inverse and the weights' gradient; the gathers
# promise their indices; the k slots lead; the counts are a compare) and
# since PR 68 did a third time (``ops/moe.choose_experts``: the choice of
# the k experts stands inside a ``custom_vjp`` whose backward is k compares
# and selects over (N, E); what left each step is one ``scatter``, the
# derivative of ``top_k`` / ``take_along_axis``: 3 -> 2 and 5 -> 4).
# MiniCPM-SALA's three were lowered at the parent of PR 46 (commit ac47f06),
# before that PR merged the runner's step bodies under them.  It renamed
# three modules and changed nothing else of their texts (CHANGES.md has the
# diffs): ``@jit_prefill_state_step`` -> ``@jit_prefill_step`` (Falcon-H1's
# prefill), ``@jit_decode_state_step`` -> ``@jit_decode_step`` (Falcon-H1's
# and MiniCPM-SALA's decode).
PARENT_LOWERINGS = {
    "falcon_h1 prefill": "f6d472b498d90e52",
    "falcon_h1 decode": "29ec77e102f27e49",
    "falcon_h1 scatter": "a42f2945b7eeeff1",
    "minicpm_sala chunk": "050e0ff1c618ee13",
    "minicpm_sala decode": "95b933bcb41266bb",
    "minicpm_sala scatter": "1b0195ec5bd06f38",
    "olmoe train": "d56f5a70d52c70f1",
    "kanana train": "105d3e7e4481e223",
}


def _digest(lowered):
    import hashlib
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


# the engine's sizes where tiny's defaults do not serve: MiniCPM-SALA's
# prompts run in chunks of 32 and its buckets are lengths in whole chunks
_SERVED = {"falcon_h1": {},
           "minicpm_sala": dict(max_model_len=128, max_prefill_tokens=128,
                                prefill_len_buckets=(32, 64, 128),
                                decode_batch_buckets=(4,))}


def _serving_lowering(family, program):
    """A family's program over its holder as shapes: the pool, the store
    and, for the family that chooses its pages, the selector's cache."""
    from ray_tpu.serve.llm import kv_cache as kvmod
    from ray_tpu.serve.llm.model_runner import ModelRunner
    S = jax.ShapeDtypeStruct

    def i32(*shape):
        return S(shape, jnp.int32)

    r = ModelRunner(engine_cfg(model=f"{family}:tiny", **_SERVED[family]))
    pool = kvmod.device_shape(64, r.kv_layers, 8, r.n_kv, r.head_dim)
    held = {"kv": S(pool, jnp.float32),
            "state": {n: S((r.state_layers, 5) + s.shape, s.dtype)
                      for n, s in r.state_spec.items()}}
    if r.select_spec:
        held["sel"] = S(kvmod.selector_shape(pool, r.select_spec["stride"]),
                        jnp.float32)
    if program == "prefill":
        return r._prefill.lower(held, r.params, i32(1, 32), i32())
    if program == "chunk":
        return r._prefill_chunk.lower(held, r.params, r.staging_spec,
                                      i32(1, r.chunk), i32(), i32())
    if program == "decode":
        return r._decode.lower(
            held, r.params, i32(4), i32(4), i32(4, r.cfg.max_blocks_per_seq),
            i32(4), i32(), i32(4), i32(4), i32(4))
    kv = S((r.kv_layers, 32, r.n_kv, r.head_dim), jnp.float32)
    return kvmod._programs().scatter_prefill.lower(held, i32(4), kv, kv,
                                                   i32(), i32())


def _train_lowering(mod, cfg):
    params = jax.eval_shape(lambda k: mod.init_params(k, cfg),
                            jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("inputs", "targets")}
    return jax.jit(jax.value_and_grad(
        lambda p, b: mod.loss_fn(p, b, cfg))).lower(params, batch)


@pytest.mark.parametrize("name", sorted(PARENT_LOWERINGS))
def test_the_shared_code_lowers_byte_for_byte_as_on_the_parent(name):
    family_name, program = name.split()
    if family_name in _SERVED:
        lowered = _serving_lowering(family_name, program)
    elif family_name == "olmoe":
        from ray_tpu.models import llama
        lowered = _train_lowering(llama, llama.tiny_moe())
    else:
        from ray_tpu.models import deepseek_v3
        lowered = _train_lowering(deepseek_v3, deepseek_v3.tiny())
    assert _digest(lowered) == PARENT_LOWERINGS[name]


def test_route_sigmoid_takes_the_divisors_epsilon():
    """Kanana's 1e-20 is the default and lowers as before; this family's
    1e-6 is another program and another number."""
    from ray_tpu.ops import moe
    x = jax.random.normal(jax.random.key(0), (5, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    bias = jnp.zeros(8)
    idx, weights = moe.route_sigmoid(x, w, bias, 2, 1.0, eps=1e-6)
    scores = jax.nn.sigmoid(x @ w)
    chosen = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(jnp.abs(weights.sum(-1) - 1).max()) > 1e-7

    def text(**kw):
        return jax.jit(lambda a, b, c: moe.route_sigmoid(a, b, c, 2, 2.5,
                                                         **kw)
                       ).lower(x, w, bias).as_text()
    assert text() == text(eps=1e-20) != text(eps=1e-6)
