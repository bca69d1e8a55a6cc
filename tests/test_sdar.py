"""Generation by diffusion over blocks (``llama:tiny-sdar``: SDAR's block at a
test's size): the block-causal mask in the dense path and in the flash
kernel, the block form of the paged decode attention, the Qwen3-MoE block
(heads of their own size, a QK-norm a head, renormalised top-k) against the
plain reference, and the serving stack stepping sequences a block at a time:
what a denoise pass and a commit pass write, the remasking rules, streams
cut by length and by a stop token, rows of one step at different passes,
preemption, and the loop keeping one pass in flight.

The engine tests run the preset in float32 (``f32_preset``): a confidence is
a softmax probability of a random model, and in bfloat16 two candidates a
rounding apart change which position a pass fixes, which is no fault.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import sdar_ref
from ray_tpu.models import llama
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import causal_attention, dense_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.serve import llm
from ray_tpu.serve.llm.kv_cache import PagedKVCache, device_shape
from ray_tpu.serve.llm.model_runner import ModelRunner, remasked

SPAN = 4
SETTINGS = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts_per_tok=2, rms_norm_eps=1e-6, rope_theta=1e6,
                block_length=SPAN)
ENGINE = dict(model="llama:tiny-sdar", block_size=8, num_blocks=64,
              max_num_seqs=4, max_model_len=96, max_prefill_tokens=96,
              decode_batch_buckets=(4,), prefill_len_buckets=(16, 32, 64, 96),
              share_weights=False)


def _masked_attention(q, k, v, span):
    """Attention under a mask written here: position i sees j where
    j // span <= i // span."""
    t = q.shape[1]
    sees = (np.arange(t)[None, :] // span) <= (np.arange(t)[:, None] // span)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = np.where(sees, scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


# ------------------------------------------------------------------ the mask
@pytest.mark.parametrize("span", [1, 4, 8])
def test_the_block_mask_in_the_dense_path_and_in_the_flash_kernel(span):
    rng = np.random.default_rng(span)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 64, 2, 32)), jnp.float32)
               for _ in range(3))
    want = _masked_attention(*map(np.asarray, (q, k, v)), span)
    dense = dense_attention(q, k, v, block=span)
    flash = flash_attention(q, k, v, span if span > 1 else True, 16, True)
    np.testing.assert_allclose(dense, want, atol=2e-6)
    np.testing.assert_allclose(flash, want, atol=2e-6)
    # and what a block calls: the choice hands the span on
    np.testing.assert_allclose(
        causal_attention(q, k, v, impl="flash", block=span), want, atol=2e-6)
    grads = [jax.grad(lambda q: (fn(q) ** 2).sum())(q) for fn in (
        lambda q: flash_attention(q, k, v, span if span > 1 else True, 16,
                                  True),
        lambda q: dense_attention(q, k, v, block=span))]
    np.testing.assert_allclose(grads[0], grads[1], atol=2e-4)


def test_a_block_of_one_is_the_causal_program_bit_for_bit():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
               for _ in range(3))
    assert np.array_equal(dense_attention(q, k, v, block=1),
                          dense_attention(q, k, v))
    assert np.array_equal(flash_attention(q, k, v, 1, 16, True),
                          flash_attention(q, k, v, True, 16, True))

    def traced(causal):
        return str(jax.make_jaxpr(
            lambda q, k, v: flash_attention(q, k, v, causal, 16, True))(
                q, k, v))
    assert traced(1) == traced(True) != traced(4)
    with pytest.raises(ValueError, match="whole blocks"):
        flash_attention(q, k, v, 3, 16, True)
    with pytest.raises(NotImplementedError, match="block-causal"):
        causal_attention(q, k, v, impl="ring", block=4)


# ------------------------------------------------- the paged attention, a block
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_a_block_of_queries_sees_the_pages_and_the_whole_block(kernel):
    """q (R, B, H, D) against dense attention over the gathered context and
    the block's own keys and values, in both directions; a row with no
    context sees its block alone."""
    rng = np.random.default_rng(1)
    layers, blocks, bs, kv, d, heads, rows = 2, 12, 16, 2, 128, 4, 3
    pool = jnp.asarray(rng.normal(size=device_shape(blocks, layers, bs, kv,
                                                    d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(blocks)[:9].reshape(3, 3), jnp.int32)
    lens = jnp.asarray([0, 20, 48], jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, SPAN, heads, d)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(rows, SPAN, kv, d)),
                                jnp.float32) for _ in range(2))
    form = (lambda *a: pa._block_decode_kernel(*a, interpret=True)) \
        if kernel else pa._block_decode_gather
    got = np.asarray(form(q, pool, 1, tables, lens, k_new, v_new))
    k_pool, v_pool = (np.asarray(x) for x in pa.heads_apart(pool[1], kv, d))
    for r in range(rows):
        n = int(lens[r])
        ctx = [np.concatenate([np.concatenate(
            [p[int(b)] for b in tables[r]])[:n], np.asarray(new[r])])
            for p, new in ((k_pool, k_new), (v_pool, v_new))]
        keys, values = (np.repeat(x, heads // kv, axis=1) for x in ctx)
        scores = np.einsum("bhd,khd->hbk", np.asarray(q[r]), keys) \
            / np.sqrt(d)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want = np.einsum("hbk,khd->bhd", probs, values)
        np.testing.assert_allclose(got[r], want, atol=3e-5)
    # the call chooses the block form from the queries' rank
    np.testing.assert_allclose(
        pa.paged_attention_decode(q, pool, 1, tables, lens, k_new, v_new),
        got, atol=3e-5)
    with pytest.raises(NotImplementedError, match="window"):
        pa.paged_attention_decode(q, pool, 1, tables, lens, k_new, v_new,
                                  window=8)


# ------------------------------------------------------------------ the model
def _f32(cfg=None):
    return dataclasses.replace(cfg or llama.tiny_sdar(), dtype=jnp.float32)


def test_the_block_against_the_reference_with_heads_of_their_own_size():
    """H x D (64) beside E (32), a QK-norm a head with scales that are not
    ones, renormalised top-2 of 8, the block mask: the program's forward in
    float32 against the plain reference; each wrong convention the
    reference can take is far off."""
    cfg = _f32()
    assert cfg.n_head * cfg.head_dim == 2 * cfg.n_embd
    params = llama.init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(0)
    for name in ("q_norm", "k_norm"):
        scale = params["blocks"][name]["scale"]
        assert scale.shape == (cfg.n_layer, cfg.head_dim)
        params["blocks"][name]["scale"] = jnp.asarray(
            rng.uniform(0.5, 1.5, scale.shape), scale.dtype)
    assert params["blocks"]["wq"]["kernel"].shape == (2, 32, 64)
    assert params["blocks"]["wo"]["kernel"].shape == (2, 64, 32)
    tokens = rng.integers(0, cfg.vocab_size, (2, 24))
    got = np.asarray(llama.forward(params, jnp.asarray(tokens), cfg))
    want = np.asarray(sdar_ref.logits(params, tokens, SETTINGS))
    assert np.abs(got - want).max() < 2e-4
    assert got.std() > 0.5                        # logits of unit spread
    for variant in (dict(qk_norm="projection"), dict(mask="causal")):
        other = np.asarray(sdar_ref.logits(params, tokens, SETTINGS,
                                           **variant))
        assert np.abs(got - other).max() > 0.05, variant
    # under the program's own choice the reference agrees and audits it
    _, _, _, ids = llama.forward_prefill(params, jnp.asarray(tokens[:1]),
                                         cfg, choices=True)
    under, audit = sdar_ref.logits(params, tokens[:1], SETTINGS, choices=ids)
    assert np.abs(got[:1] - np.asarray(under)).max() < 2e-4
    assert audit == {"decisions": 2 * 24, "differing": 0,
                     "worst_margin": 0.0}


def test_the_presets_say_how_they_are_stepped():
    assert llama.block_stepping(llama.tiny_sdar()) == {
        "block": 4, "mask_id": 199, "per_pass": 1}
    assert llama.block_stepping(dataclasses.replace(
        llama.tiny_sdar(), denoising_steps=2))["per_pass"] == 2
    assert llama.block_stepping(llama.tiny_moe()) is None
    assert llama.block_stepping(llama.tiny()) is None
    with pytest.raises(ValueError, match="mask_token_id"):
        llama.block_stepping(dataclasses.replace(llama.tiny_sdar(),
                                                 mask_token_id=None))
    with pytest.raises(ValueError, match="denoising steps"):
        llama.block_stepping(dataclasses.replace(llama.tiny_sdar(),
                                                 denoising_steps=3))
    with pytest.raises(ValueError, match="whole blocks"):
        ModelRunner(llm.EngineConfig(**{**ENGINE, "block_size": 6}))


# ------------------------------------------- the experts of a stack of layers
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_layers_experts_out_of_the_whole_stacks_run_of_groups(layer):
    """``dropless_moe_ffn(stack_at=layer)`` on the experts of three layers
    as one run of groups is the call on that layer's experts alone: the
    output, the ids chosen and the stats."""
    from ray_tpu.ops import moe
    rng = np.random.default_rng(3)
    n, d, f, X, k, L = 24, 32, 16, 8, 2, 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_router = jnp.asarray(rng.normal(size=(d, X)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(L, X, *shape)) / 6, jnp.float32)
          for shape in ((d, f), (d, f), (f, d))]
    live = jnp.arange(n) < 20
    want, stats, ids = moe.dropless_moe_ffn(
        x, w_router, *(w[layer] for w in ws), k=k, norm_topk=True,
        choices=True, live=live)
    got, got_stats, got_ids = jax.jit(
        lambda at: moe.dropless_moe_ffn(
            x, w_router, *(w.reshape(L * X, *w.shape[2:]) for w in ws), k=k,
            norm_topk=True, choices=True, live=live, stack_at=at))(
                jnp.int32(layer))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert (np.asarray(got_ids) == np.asarray(ids)).all()
    assert got_ids.max() < X
    for a, b in zip(got_stats, stats):
        np.testing.assert_allclose(a, b, rtol=1e-6)


# -------------------------------------------------------- the remasking rule
def _logits(conf, token, vocab=16):
    """Logits whose argmax is ``token`` with softmax probability ``conf``,
    the rest spread evenly."""
    rest = np.log((1 - conf) / (vocab - 1))
    out = np.full(vocab, rest)
    out[token] = np.log(conf)
    return out


def _rule_loop(confs, tokens, block, decided, per_pass):
    """The rule written here, a row at a time."""
    block, decided = list(block), list(decided)
    left = [i for i in range(len(block)) if not decided[i]]
    for i in sorted(left, key=lambda i: (-confs[i], i))[:per_pass]:
        block[i], decided[i] = tokens[i], True
    return block, decided


@pytest.mark.parametrize("per_pass", [1, 2, 4])
def test_the_rule_against_a_loop_written_here(per_pass):
    rng = np.random.default_rng(7)
    rows = []
    for _ in range(24):
        confs = rng.permutation([0.2, 0.3, 0.5, 0.8, 0.95, 0.97])[:SPAN]
        rows.append((confs, rng.integers(0, 16, SPAN),
                     rng.integers(0, 16, SPAN), rng.random(SPAN) < 0.4))
    # a tie (the same logits at every position): the lowest position first
    rows.append((np.full(SPAN, 0.5), np.full(SPAN, 3), np.arange(SPAN) + 8,
                 np.zeros(SPAN, bool)))
    rows.append((np.full(SPAN, 0.5), np.arange(SPAN), np.arange(SPAN) + 8,
                 np.ones(SPAN, bool)))                # a commit: untouched
    logits = jnp.asarray([[_logits(c, t) for c, t in zip(confs, tokens)]
                          for confs, tokens, _, _ in rows], jnp.float32)
    block = jnp.asarray([r[2] for r in rows], jnp.int32)
    decided = jnp.asarray([r[3] for r in rows])
    got_block, got_decided, conf = remasked(logits, block, decided, per_pass)
    for i, (confs, tokens, was, flags) in enumerate(rows):
        want_block, want_decided = _rule_loop(confs, tokens, was, flags,
                                              per_pass)
        assert list(got_decided[i]) == want_decided, (i, confs, flags)
        assert list(got_block[i]) == want_block
        np.testing.assert_allclose(conf[i], confs, rtol=1e-5)


def test_a_pass_fixes_no_more_than_are_undecided():
    """Three of four decided and two to fix a pass: the one left is fixed
    and nothing else moves; nothing undecided: the block as it was."""
    logits = jnp.asarray([[_logits(c, 3) for c in (0.95, 0.3, 0.92, 0.5)]],
                         jnp.float32)
    was = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    block, decided, _ = remasked(
        logits, was, jnp.asarray([[True, False, True, True]]), 2)
    assert list(decided[0]) == [True] * 4
    assert list(block[0]) == [7, 3, 9, 10]
    block, decided, _ = remasked(logits, was, jnp.ones((1, 4), bool), 2)
    assert list(block[0]) == [7, 8, 9, 10] and all(decided[0])


# ----------------------------------------------------------- the runner alone
@pytest.fixture(scope="module")
def f32_preset():
    """``llama:tiny-sdar`` in float32 for this module's engines."""
    was = llama.PRESETS["tiny-sdar"]
    llama.PRESETS["tiny-sdar"] = lambda: _f32(was())
    yield
    llama.PRESETS["tiny-sdar"] = was


@pytest.fixture(scope="module")
def runner(f32_preset):
    cfg = llm.EngineConfig(**ENGINE)
    one = ModelRunner(cfg)
    one.cache = PagedKVCache.for_engine(cfg, one.family.kept)
    return one


def _tables(runner, sid):
    tables = np.zeros((1, runner.cfg.max_blocks_per_seq), np.int32)
    table = runner.cache.table(sid)
    tables[0, :len(table)] = table
    return tables


@pytest.mark.parametrize("n", [20, 21, 23], ids=["n%4=0", "n%4=1", "n%4=3"])
def test_every_pass_of_three_blocks_against_the_reference(runner, n):
    """Prefill, then three blocks through ``runner.decode`` as the engine
    drives them; each pass's logits against one plain forward over the
    committed ids and the block as fed, masks included.  A commit pass's
    K/V is thereby judged by the block after it."""
    cache, mask = runner.cache, runner.block["mask_id"]
    rng = np.random.default_rng(n)
    prompt = [int(t) for t in rng.integers(0, 199, n)]
    whole, sid = n // SPAN * SPAN, f"ref{n}"

    def reference(fed):
        return np.asarray(sdar_ref.logits(runner.params, [fed], SETTINGS))[0]

    cache.alloc_seq(sid, whole)
    try:
        logits, ks, vs = runner.prefill(prompt[:whole])
        assert logits.shape == (SPAN, 200)
        assert np.abs(logits - reference(prompt[:whole])[-SPAN:]).max() < 2e-4
        cache.scatter_prefill(sid, ks, vs, whole)
        done, given, passes = prompt[:whole], prompt[whole:], 0
        for _ in range(3):
            cache.append_block(sid, SPAN)
            at = np.asarray([len(done)], np.int32)
            ids = given + [mask] * (SPAN - len(given))
            decided = [True] * len(given) + [False] * (SPAN - len(given))
            given = []
            while True:
                commits = all(decided)
                chosen, _, _ = runner.decode(
                    np.asarray([ids]), at, cache.pool, _tables(runner, sid),
                    at, decided=np.asarray([decided]),
                    commit=np.asarray([commits]), logit_rows=(0,))
                fed = [t if d else mask for t, d in zip(ids, decided)]
                want = reference(done + fed)[-SPAN:]
                assert np.abs(chosen.logits[0] - want).max() < 2e-4
                passes += 1
                if commits:
                    assert list(chosen.ids[0]) == ids
                    done = done + fed
                    break
                # the static rule fixed exactly one position more, its
                # token the argmax of its own logits
                now = [bool(d) for d in chosen.decided[0]]
                new, = [i for i in range(SPAN) if now[i] and not decided[i]]
                assert chosen.ids[0][new] == want[new].argmax()
                ids, decided = [int(t) for t in chosen.ids[0]], now
        assert passes == 3 * 5 - (n - whole)
    finally:
        cache.free_seq(sid)


def test_a_denoise_pass_writes_nothing_and_a_commit_its_blocks_slots(runner):
    cache = runner.cache
    prompt = [int(t) for t in np.random.default_rng(5).integers(0, 199, 12)]
    cache.alloc_seq("w", 12)
    try:
        _, ks, vs = runner.prefill(prompt)
        cache.scatter_prefill("w", ks, vs, 12)
        cache.append_block("w", SPAN)
        before = cache.blocks().copy()
        at = np.asarray([12], np.int32)
        block = np.asarray([[5, 6, 7, 8]], np.int32)
        runner.decode(block, at, cache.pool, _tables(runner, "w"), at,
                      decided=np.asarray([[True, False, True, False]]))
        assert np.array_equal(before, cache.blocks())
        _, k, v = runner.decode(block, at, cache.pool, _tables(runner, "w"),
                                at, commit=np.asarray([True]))
        after = cache.blocks()
        changed = np.argwhere(np.abs(after - before).sum((1, 2, 4, 5)) > 0)
        page = cache.table("w")[1]
        assert changed.tolist() == [[page, 4 + j] for j in range(SPAN)]
        # what it wrote is the pass's own K and V of the block's positions
        np.testing.assert_allclose(after[page, :, 0, 4:8],
                                   np.asarray(k[:, 0], np.float32))
        np.testing.assert_allclose(after[page, :, 1, 4:8],
                                   np.asarray(v[:, 0], np.float32))
        # a greedy row pulls ids, confidences and flags, never logits
        chosen, _, _ = runner.decode(block, at, cache.pool,
                                     _tables(runner, "w"), at, logit_rows=())
        assert chosen.logits == {} and chosen.ids.shape == (1, SPAN)
        assert chosen.conf.dtype == np.float32 and chosen.decided.all()
        assert chosen.reads["pages_read"] == 2 * 2      # 2 pages x 2 layers
    finally:
        cache.free_seq("w")
    with pytest.raises(ValueError, match="whole blocks"):
        runner.prefill(prompt[:10])


# ------------------------------------------------------------------ the engine
def _reference_stream(params, prompt, max_tokens, stop=None, per_pass=1):
    """The generation loop written here on the plain reference: whole prompt
    blocks are given, a block is denoised by the rule until nothing is
    undecided, its new tokens go out in order, cut at ``max_tokens`` and at
    ``stop``."""
    seq, out, mask = list(prompt), [], 199
    at = len(seq) // SPAN * SPAN
    while len(out) < max_tokens:
        given = seq[at:]
        block = given + [mask] * (SPAN - len(given))
        decided = [True] * len(given) + [False] * (SPAN - len(given))
        while not all(decided):
            fed = [t if d else mask for t, d in zip(block, decided)]
            logits = np.asarray(sdar_ref.logits(
                params, [seq[:at] + fed], SETTINGS))[0][at:]
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            conf = probs.max(-1) / probs.sum(-1)
            block, decided = _rule_loop(conf, logits.argmax(-1), block,
                                        decided, per_pass)
        for tok in block[len(given):]:
            out.append(int(tok))
            if len(out) == max_tokens or tok == stop:
                return out
        seq, at = seq[:at] + block, at + SPAN
    return out


def _drive(eng, prompts, **sampling):
    streams = [eng.submit(p, llm.SamplingParams(**sampling)) for p in prompts]
    while eng._work_pending():
        eng.step()
    return [s.tokens() for s in streams]


@pytest.fixture(scope="module")
def engine(f32_preset):
    eng = llm.LLMEngine(llm.EngineConfig(**ENGINE), start=False)
    yield eng
    eng.shutdown()


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 199, n)] for n in lengths]


def test_streams_are_the_reference_loops_and_rows_stand_at_different_passes(
        engine):
    """Five prompts with n % 4 in {0, 1, 2, 3} through four slots at once:
    rows of one step stand at different passes of their blocks (some
    denoise, some commit, some begin a block) and each sequence gets what
    it gets alone; a stream holds exactly max_tokens tokens, a multiple of
    the block or not."""
    before = engine.stats()
    prompts = _prompts(11, (14, 12, 13, 21, 30))
    want = [_reference_stream(engine.runner.params, p, 9) for p in prompts]
    assert _drive(engine, prompts, max_tokens=9) == want
    alone = _drive(engine, prompts[:1], max_tokens=9)
    assert alone == want[:1]
    after = engine.stats()
    assert after["blocks_free"] == before["blocks_free"] == 64
    passes = {k: after["block_passes"][k] - before["block_passes"][k]
              for k in ("denoise", "commit")}
    blocks = after["blocks_committed"] - before["blocks_committed"]
    assert passes["commit"] == blocks
    # the loop kept a pass in flight: most passes were enqueued behind one
    steps = after["decode_steps"] - before["decode_steps"]
    ahead = after["decode_steps_ahead"] - before["decode_steps_ahead"]
    assert ahead > 0.8 * steps
    # 9 tokens: the last block is cut wherever a first block was given some
    assert after["blocks_lost"]["cut"] - before["blocks_lost"]["cut"] >= 4
    assert after["blocks_lost"]["preempt"] == 0
    assert set(after) - {"blocks_committed", "block_passes",
                         "blocks_lost"} >= {
        "prefill_steps", "decode_steps", "running", "waiting", "preemptions"}


def test_a_stop_token_inside_a_block_ends_the_stream_at_it(engine):
    prompt, = _prompts(12, (15,))
    free = engine.stats()["blocks_free"]
    whole = _reference_stream(engine.runner.params, prompt, 12)
    stop = whole[2]                  # inside the second block (1 + 4 + ...)
    cut = whole[:whole.index(stop) + 1]
    before = engine.stats()["blocks_lost"]["stop"]
    stream = engine.submit(prompt, llm.SamplingParams(max_tokens=12,
                                                      stop_token=stop))
    while engine._work_pending():
        engine.step()
    assert stream.tokens() == cut and stream.finish_reason == "stop"
    assert engine.stats()["blocks_lost"]["stop"] == before + 1
    assert engine.stats()["blocks_free"] == free    # every slot went back


def test_a_prompt_may_hold_the_mask_id_as_a_token(engine):
    """Which positions are undecided the engine knows by position: a given
    token that is the mask id stays given, in a whole block and in the
    part-block that opens the first block."""
    prompt, = _prompts(13, (14,))
    prompt[5] = prompt[12] = prompt[13] = 199
    want = _reference_stream(engine.runner.params, prompt, 8)
    assert _drive(engine, [prompt], max_tokens=8) == [want]


def test_a_request_that_samples_is_refused(engine):
    with pytest.raises(ValueError, match="samples"):
        engine.submit([1, 2, 3, 4], llm.SamplingParams(temperature=0.7))
    with pytest.raises(NotImplementedError, match="stepped by blocks"):
        engine.prefill_remote([1, 2, 3, 4])


def test_preemption_mid_block_folds_committed_tokens_alone(f32_preset):
    """A pool too small for three sequences: the latest is preempted with a
    block open, its committed tokens fold into the prompt, it is prefilled
    again in whole blocks and its stream is what it is alone."""
    eng = llm.LLMEngine(llm.EngineConfig(**{**ENGINE, "num_blocks": 8}),
                        start=False)
    try:
        prompts = _prompts(14, (13, 22, 19))
        want = [_reference_stream(eng.runner.params, p, 16) for p in prompts]
        assert _drive(eng, prompts, max_tokens=16) == want
        stats = eng.stats()
        assert stats["preemptions"] > 0
        assert stats["blocks_lost"]["preempt"] > 0
        assert stats["decode_drains"]["pressure"] > 0
        assert stats["blocks_free"] == 8
    finally:
        eng.shutdown()


def test_two_positions_a_pass_and_a_prompt_shorter_than_a_block(f32_preset):
    """``denoising_steps`` 2: a pass fixes two positions, a block is two
    denoise passes and a commit pass, and the loop still keeps one pass in
    flight.  A prompt of fewer tokens than a block prefills nothing: it is
    the given part of the first block."""
    was = llama.PRESETS["tiny-sdar"]
    llama.PRESETS["tiny-sdar"] = lambda: dataclasses.replace(
        was(), denoising_steps=2)
    try:
        eng = llm.LLMEngine(llm.EngineConfig(**ENGINE), start=False)
    finally:
        llama.PRESETS["tiny-sdar"] = was
    try:
        prompts = _prompts(15, (16, 3))
        want = [_reference_stream(eng.runner.params, p, 8, per_pass=2)
                for p in prompts]
        assert _drive(eng, prompts, max_tokens=8) == want
        stats = eng.stats()
        assert stats["prefill_steps"] == 1
        assert stats["decode_steps_ahead"] > 0
        # the prompt of 3 gives its first block 3 positions: one denoise
        # pass there, two in every other block
        assert stats["block_passes"]["commit"] == stats["blocks_committed"]
        assert stats["block_passes"]["denoise"] \
            == 2 * stats["blocks_committed"] - 1
    finally:
        eng.shutdown()


def test_a_commit_names_the_sequences_it_gave_tokens(engine, monkeypatch):
    """The spans' contract (a token is on its stream at the end of the
    commit that names its sequence): a prompt's ``llm.prefill.commit`` names
    nobody, it yields no token; ``llm.decode.commit`` names the sequences
    its commit passes gave tokens, with the tokens of each, and a commit
    that read denoise passes alone names nobody."""
    from ray_tpu.serve.llm import engine as engine_mod
    seen = []

    class Recorded(engine_mod.hot_span):
        __slots__ = ("attrs",)

        def __init__(self, name, totals, **attrs):
            super().__init__(name, totals, **attrs)
            self.attrs = attrs
            seen.append(self)

        def set(self, **attrs):
            self.attrs.update(attrs)
            super().set(**attrs)

    monkeypatch.setattr(engine_mod, "hot_span", Recorded)
    streams = _drive(engine, _prompts(21, (9, 16)), max_tokens=6)
    assert [len(s) for s in streams] == [6, 6]
    first = [e for e in seen if e.name == "llm.prefill.commit"]
    assert len(first) == 2 and not any("seq" in e.attrs for e in first)
    commits = [e.attrs for e in seen if e.name == "llm.decode.commit"]
    told = {}
    for attrs in commits:
        members = [m for m in attrs["seqs"].split("|") if m]
        each = [int(t) for t in attrs["tokens_by_seq"].split("|") if t]
        assert len(members) == len(each) == attrs["blocks"]
        assert sum(each) == attrs["tokens"]
        for sid, n in zip(members, each):
            told.setdefault(sid, []).append(n)
    # the prompt of 9 gives its first block one position: 3 tokens, then 3
    # of the next block's 4 (cut at max_tokens); the prompt of 16: 4, then 2
    assert sorted(told.values()) == [[3, 3], [4, 2]]
    assert any(not attrs["blocks"] for attrs in commits)
    passes = [e.attrs for e in seen if e.name == "llm.decode"
              and "block" in e.attrs]
    assert passes and all(a["block"] == SPAN and a["denoise"] + a["commit"]
                          == a["batch"] for a in passes)


def test_a_token_stepped_preset_runs_the_loop_it_ran():
    """``block_stepping`` answers None: the runner has no block, the step
    program takes one token a row, and ``stats()`` has the keys it had."""
    eng = llm.LLMEngine(llm.EngineConfig(**{**ENGINE,
                                            "model": "llama:tiny-moe",
                                            "max_model_len": 48,
                                            "max_prefill_tokens": 48,
                                            "prefill_len_buckets": (16, 32,
                                                                    48)}),
                        start=False)
    try:
        assert eng.runner.block is None
        out = _drive(eng, _prompts(16, (9, 14)), max_tokens=6)
        assert [len(o) for o in out] == [6, 6]
        stats = eng.stats()
        assert not {"blocks_committed", "block_passes",
                    "blocks_lost"} & set(stats)
        assert set(stats["decode_drains"]) == {"sampled", "pressure",
                                               "admit", "tail"}
        assert stats["decode_steps_ahead"] > 0
    finally:
        eng.shutdown()
