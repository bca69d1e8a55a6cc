"""Falcon-H1 at ``falcon_h1:tiny`` on the CPU: the mixer's pieces
(``ops/ssm.py``), the model against the plain float32 reference
(``perfbench/reference/falcon_h1_ref.py``), and the recurrent state
through the serving engine: rows beside blocks in one cache manager.

Tolerances.  The engine tests run the model in float32 (the preset's
``dtype`` patched), where program and reference differ by summation order
only: 1e-5 of logits whose standard deviation is 1.  ``TIGHT`` = 2e-4 is
twenty times that, and a state kept in bf16 (0.4% a step), a skipped
multiplier, a conv tail from padding or a neighbour's row each move
logits by 1e-2 or more (shown below where it is cheap to show).  In bf16
the tiny model's two layers differ from the reference by 0.04-0.05;
``BF16`` = 0.15 is three times that, as the cells' ``logit_atol`` are set.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from perfbench.families import falcon_h1 as family
from perfbench.reference import falcon_h1_ref
from ray_tpu.models import falcon_h1 as fh
from ray_tpu.ops import ssm
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.kv_cache import (NoFreeBlocks, PagedKVCache,
                                       device_shape)

TIGHT, BF16 = 2e-4, 0.15


def _f32(cfg):
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _sizes(cfg):
    """The reference's sizes (config.json names) of a program config."""
    return family.sizes_of_model(cfg)


@pytest.fixture
def f32_tiny(monkeypatch):
    """``falcon_h1:tiny`` resolves to the tiny model in float32."""
    cfg = _f32(fh.tiny())
    monkeypatch.setitem(fh.PRESETS, "tiny", lambda: cfg)
    return cfg


def engine_cfg(**kw):
    base = dict(model="falcon_h1:tiny", num_blocks=64, block_size=8,
                max_num_seqs=4, max_model_len=64, max_prefill_tokens=32,
                prefill_len_buckets=(16, 32, 64),
                decode_batch_buckets=(1, 2, 4), share_weights=False)
    base.update(kw)
    return EngineConfig(**base)


# ------------------------------------------------------------------ the ops
def _recurrence(x, dt, a, b, c, upto):
    """Token by token with ssm_step, over the first ``upto`` positions."""
    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        y_t, state = ssm.ssm_step(state, x_t, dt_t, a, b_t, c_t)
        return state, y_t

    bsz, _, h, p = x.shape
    state = jnp.zeros((bsz, h, p, b.shape[-1]))
    state, y = lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0)[:upto] for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


@pytest.mark.parametrize("length,chunk,last", [
    (37, 8, 36),        # not a multiple of the chunk, no padding
    (37, 8, 29),        # last_pos inside a chunk, 7 padded positions
    (32, 8, 15),        # last_pos at a chunk's edge
    (5, 128, 2),        # a sequence shorter than one chunk
    (64, 16, 0),        # a one-token prompt in a 64 bucket
])
def test_chunked_scan_is_the_recurrence_and_freezes_past_last_pos(
        length, chunk, last):
    keys = jax.random.split(jax.random.key(length + last), 5)
    bsz, h, p, g, n = 2, 4, 16, 2, 16
    x = jax.random.normal(keys[0], (bsz, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bsz, length, h)) - 2)
    a = -jnp.exp(jax.random.normal(keys[2], (h,)))
    b = jax.random.normal(keys[3], (bsz, length, g, n))
    c = jax.random.normal(keys[4], (bsz, length, g, n))
    real = jnp.arange(length)[None, :, None] <= last
    y, state = ssm.ssd_scan(x, jnp.where(real, dt, 0.0), a, b, c, chunk)
    want_y, want_state = _recurrence(x, dt, a, b, c, last + 1)
    # float32 sums in another order: 1e-5 of values of order 1-10
    np.testing.assert_allclose(y[:, :last + 1], want_y, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)
    # not frozen, the padding is folded in and the state is another one
    if last + 1 < length:
        _, thawed = ssm.ssd_scan(x, dt, a, b, c, chunk)
        assert np.abs(thawed - want_state).max() > 1e-2


@pytest.mark.parametrize("last", [0, 1, 2, 9, 15])
def test_conv_tail_is_the_last_real_inputs_and_the_step_goes_on(last):
    keys = jax.random.split(jax.random.key(last), 3)
    x = jax.random.normal(keys[0], (2, 16, 6))
    w = jax.random.normal(keys[1], (4, 6))
    bias = jax.random.normal(keys[2], (6,))
    y, tail = ssm.causal_conv(x, w, bias, jnp.int32(last))
    want = np.zeros((2, 3, 6), np.float32)
    have = np.asarray(x[:, max(0, last - 2):last + 1])
    want[:, 3 - have.shape[1]:] = have
    np.testing.assert_array_equal(tail, want)
    # y_t by the definition, and the step from the tail gives y_{last+1}
    t = min(last + 1, 15)
    window = np.concatenate([np.zeros((2, 3, 6), np.float32), x], 1)
    np.testing.assert_allclose(
        y[:, t], bias + (w * window[:, t:t + 4]).sum(1), atol=1e-5)
    if last + 1 < 16:
        y_next, tail_next = ssm.conv_step(tail, x[:, last + 1], w, bias)
        np.testing.assert_allclose(y_next, y[:, last + 1], atol=1e-5)
        np.testing.assert_array_equal(tail_next[:, -1], x[:, last + 1])


# ---------------------------------------------------------------- the model
@pytest.fixture(scope="module")
def tiny_params():
    return fh.init_params(jax.random.key(1), fh.tiny())


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, TIGHT),
                                        (jnp.bfloat16, BF16)])
def test_forward_agrees_with_the_plain_reference(tiny_params, dtype, atol):
    cfg = dataclasses.replace(fh.tiny(), dtype=dtype)
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab_size)
    want = falcon_h1_ref.logits(tiny_params, tokens, _sizes(cfg))
    got = fh.forward(tiny_params, tokens, cfg)
    assert 0.5 < float(np.std(want)) < 2.0      # logits a check can fail on
    np.testing.assert_allclose(got, want, atol=atol)


@pytest.mark.parametrize("field", [
    "ssm_in_multiplier", "ssm_out_multiplier", "key_multiplier",
    "attention_out_multiplier", "embedding_multiplier",
    "lm_head_multiplier", "ssm_multipliers", "mlp_multipliers"])
def test_a_skipped_multiplier_fails_the_tolerance(tiny_params, field):
    cfg = _f32(fh.tiny())
    ones = 1.0 if isinstance(getattr(cfg, field), float) \
        else tuple(1.0 for _ in getattr(cfg, field))
    tokens = jax.random.randint(jax.random.key(2), (1, 24), 0, cfg.vocab_size)
    want = falcon_h1_ref.logits(tiny_params, tokens, _sizes(cfg))
    got = fh.forward(tiny_params, tokens,
                     dataclasses.replace(cfg, **{field: ones}))
    assert np.abs(got - want).max() > 50 * TIGHT


@pytest.mark.parametrize("n,bucket", [(13, 16), (16, 16), (21, 32)])
def test_prefill_in_a_bucket_is_the_unpadded_prompt(tiny_params, n, bucket):
    """Logits, K/V and the recurrent state at ``last_pos`` of a padded
    prompt are those of the prompt alone: the padding reaches nothing."""
    cfg = _f32(fh.tiny())
    prompt = jax.random.randint(jax.random.key(n), (1, n), 0, cfg.vocab_size)
    padded = jnp.pad(prompt, ((0, 0), (0, bucket - n)), constant_values=7)
    last = jnp.int32(n - 1)
    logits, ks, vs, state = fh.forward_prefill(tiny_params, padded, cfg, last)
    want, wk, wv, wstate = fh.forward_prefill(tiny_params, prompt, cfg, last)
    np.testing.assert_allclose(logits, want, atol=TIGHT)
    np.testing.assert_allclose(ks[:, :, :n], wk, atol=TIGHT)
    for name, spec in fh.recurrent_state(cfg).items():
        assert state[name].shape == (cfg.n_layer, 1) + spec.shape
        assert state[name].dtype == spec.dtype == jnp.float32
        np.testing.assert_allclose(state[name], wstate[name], atol=TIGHT)
    ref = falcon_h1_ref.logits(tiny_params, prompt, _sizes(cfg))
    np.testing.assert_allclose(logits, ref[:, -1], atol=TIGHT)


def test_init_draws_the_type_it_is_told_and_keeps_the_wide_leaves():
    cfg = dataclasses.replace(fh.tiny(), param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda k: fh.init_params(k, cfg),
                            jax.random.key(0))
    from ray_tpu.models._common import serving_params
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        wide = any(getattr(k, "key", None) in fh.WIDE_PARAMS for k in path)
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16), path
    # so the tree has its serving type already: it is served as it is
    real = fh.init_params(jax.random.key(0), cfg)
    assert serving_params(real, cfg.dtype, fh.WIDE_PARAMS) is real
    big = fh.PRESETS["falcon-h1-34b-l6"]()
    assert big.param_dtype == big.dtype == jnp.bfloat16 and big.n_layer == 6
    assert big.ssm_proj_segments == (4096, 4096, 512, 512, 32)
    assert sum(big.ssm_proj_segments) == 9248 and big.conv_dim == 5120


# ----------------------------------------------------------- the cache rows
def _stateful_cache(**kw):
    return PagedKVCache(16, 2, 4, 2, 8, state=fh.recurrent_state(fh.tiny()),
                        max_seqs=2, **kw)


def test_a_row_is_given_with_the_blocks_and_taken_back_with_them():
    cache = _stateful_cache()
    spec = fh.recurrent_state(fh.tiny())
    held = cache.pool.read(lambda h: h)
    assert set(held) == {"kv", "state"} and set(held["state"]) == set(spec)
    for name, s in spec.items():
        assert held["state"][name].shape == (2, 3) + s.shape    # 2 + staging
    assert cache.state_rows == 2 and cache.staging_row == 2
    assert cache.state_bytes == sum(
        2 * 3 * int(np.prod(s.shape)) * 4 for s in spec.values())
    a = cache.alloc_seq("a", 6)
    b = cache.alloc_seq("b", 3)
    assert cache.state_rows_used() == 2
    assert {cache.state_row("a"), cache.state_row("b")} == {0, 1}
    free = cache.free_block_count()
    with pytest.raises(NoFreeBlocks, match="rows of recurrent state"):
        cache.alloc_seq("c", 1)
    assert cache.free_block_count() == free          # and no block leaked
    tables = np.zeros((3, 4), np.int32)
    tables[0, :2], tables[1, :1], tables[2, 0] = a, b, 15
    np.testing.assert_array_equal(
        cache.rows_of(tables),
        [cache.state_row("a"), cache.state_row("b"), 3])  # 3: outside
    cache.free_seq("a")
    assert cache.state_rows_used() == 1
    assert cache.rows_of(tables)[0] == 3
    cache.alloc_seq("c", 1)
    cache.free_seq("b"), cache.free_seq("c")
    assert cache.state_rows_used() == 0 and cache.free_block_count() == 16
    with pytest.raises(NotImplementedError, match="forked"):
        cache.fork_seq("a", "b")


def test_a_cache_without_state_holds_the_pool_and_nothing_beside_it():
    cache = PagedKVCache(4, 2, 4, 2, 8)
    assert cache.state_rows == 0 and cache.state_bytes == 0
    # the one form of what is held: a dict, here of the pool alone
    held = cache.pool.read(lambda held: held)
    assert list(held) == ["kv"] == list(cache.pool.abstract())
    # (L, 2, N, bs, F): 2 x 8 = 16 lanes in use of the tile's 128
    assert held["kv"].shape == (2, 2, 4, 4, 128) \
        == device_shape(4, 2, 4, 2, 8)
    cache.alloc_seq("a", 3)
    assert cache.state_rows_used() == 0


# ---------------------------------------------------------------- the engine
def _recorded(eng):
    """Logits of every prefill and decode the engine's loop runs, by the
    sequence whose row of the batch they are: {seq id: [logits, ...]}."""
    got = {}
    runner, prefill_one = eng.runner, eng._prefill_one
    prefill, decode = runner.prefill, runner.decode
    current = []

    def spy_prefill_one(seq, span):
        current[:] = [seq.seq_id]
        return prefill_one(seq, span)

    # the loop names no row of a greedy batch: the spies ask for every
    # row, and hand the loop the ids the step chose beside them
    def spy_prefill(token_ids, *, logit_rows):
        chosen, ks, vs = prefill(token_ids, logit_rows=(0,))
        got.setdefault(current[0], []).append(
            (len(token_ids), chosen.logits[0]))
        return chosen, ks, vs

    def spy_decode(tokens, positions, pool, tables, lens, *, logit_rows,
                   **ahead):
        # the loop pulls a step after it has enqueued the next: the spy
        # reads this one's logits now, which waits for it
        step, ks, vs = decode(tokens, positions, pool, tables, lens,
                              logit_rows=range(len(tokens)), **ahead)
        chosen = runner.pull_step(step)
        owners = [eng.cache._owner[int(t[0])] for t in tables]
        for row, (sid, at) in enumerate(zip(owners, positions)):
            got[sid].append((int(at) + 1, chosen.logits[row]))
        return step, ks, vs

    eng._prefill_one = spy_prefill_one
    runner.prefill, runner.decode = spy_prefill, spy_decode
    return got


def _assert_logits_are_the_references(eng, cfg, got, streams, atol=TIGHT):
    for stream, prompt, output in streams:
        full = np.asarray([list(prompt) + list(output)], np.int32)
        ref = np.asarray(falcon_h1_ref.logits(
            eng.runner.params, full, _sizes(cfg)))[0]
        assert got[stream.seq_id], stream.seq_id
        for n_ctx, logits in got[stream.seq_id]:
            # logits after n_ctx tokens of the sequence predict token n_ctx
            np.testing.assert_allclose(logits, ref[n_ctx - 1], atol=atol)


def test_engine_loop_prefill_then_decode_is_the_reference(f32_tiny):
    """One sequence through LLMEngine's own loop: every step's logits are
    the reference's full forward over prompt + output (a 13-token prompt
    in the 16 bucket: 3 padded positions), and the spans and counters
    of the recurrent state are there."""
    eng = LLMEngine(engine_cfg())
    try:
        got = _recorded(eng)
        prompt = list(range(3, 16))
        stream = eng.submit(prompt, SamplingParams(max_tokens=12))
        output = stream.tokens()
        assert len(output) == 12
        _assert_logits_are_the_references(eng, f32_tiny, got,
                                          [(stream, prompt, output)])
        assert len(got[stream.seq_id]) == 12
        stats = eng.stats()
        assert stats["state_rows"] == 4 and stats["state_rows_used"] == 0
        assert stats["state_commits"] == 1
        assert stats["state_rows_stepped"] == 5 * stats["decode_steps"]
        assert stats["state_bytes"] == eng.cache.state_bytes > 0
        assert stats["span_s"]["llm.prefill.scatter"][0] == 1
    finally:
        eng.shutdown()


def test_interleaved_sequences_keep_to_their_own_rows(f32_tiny):
    """Six sequences over four slots, arriving and finishing at different
    steps, so that rows are handed on and the batch order changes: each
    one's logits are those of its own full forward."""
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
    the re-prefill over prompt + output rebuilds the state, and the
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
        # and every step's logits, the recomputed ones too
        _assert_logits_are_the_references(
            eng, f32_tiny, got, list(zip(streams, prompts, outs)))
        stats = eng.stats()
        assert stats["state_rows_used"] == 0
        assert stats["state_commits"] == stats["prefill_steps"] > 3
        assert eng.cache.free_block_count() == 6
    finally:
        eng.shutdown(), big.shutdown()


def test_rows_come_back_on_cancel(f32_tiny):
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
            import time
            time.sleep(0.02)
        assert eng.stats()["state_rows_used"] == 0
        assert eng.cache.free_block_count() == 64
        # and the freed rows serve again
        assert len(eng.generate([9, 9, 9], SamplingParams(max_tokens=3))) == 3
    finally:
        eng.shutdown()


def test_export_and_import_refuse_a_model_with_recurrent_state(f32_tiny):
    eng = LLMEngine(engine_cfg(), start=False)
    try:
        with pytest.raises(NotImplementedError, match="recurrent state"):
            eng.prefill_remote([1, 2, 3])
        with pytest.raises(NotImplementedError, match="recurrent state"):
            eng.attach({"model": "falcon_h1:tiny"})
        assert eng.stats()["state_rows_used"] == 0
        assert eng.stats()["prefill_steps"] == 0
    finally:
        eng.shutdown()


def test_a_runner_on_its_own_says_where_the_state_lives():
    from ray_tpu.serve.llm.model_runner import ModelRunner
    runner = ModelRunner(engine_cfg())
    assert set(runner.state_spec) == {"ssm", "conv"}
    with pytest.raises(RuntimeError, match="engine's cache"):
        runner.prefill([1, 2, 3])


def test_bf16_engine_stays_within_the_bf16_tolerance():
    """The preset as it is (bf16 activations): 24 decode steps after a
    prefill stay within the tolerance of its kind; the state itself is
    float32 in the store."""
    eng = LLMEngine(engine_cfg())
    try:
        got = _recorded(eng)
        prompt = list(range(40, 61))
        stream = eng.submit(prompt, SamplingParams(max_tokens=24))
        output = stream.tokens()
        _assert_logits_are_the_references(
            eng, fh.tiny(), got, [(stream, prompt, output)], atol=BF16)
        store = eng.cache.pool.read(lambda held: held["state"])
        assert all(leaf.dtype == jnp.float32 for leaf in store.values())
    finally:
        eng.shutdown()


# ------------------------------------------------- the stateless families
# sha256 (first 16 digits) of each program's StableHLO as the parent of the
# PR that added recurrent state lowered it (commit 2c891de, this jax): the
# stateless path did not grow a branch.  A PR that changes one of these
# programs on purpose lowers them again and replaces the digests: PR 33
# did so for ``prefill`` and ``decode`` of both families (each returns
# its rows' greedy ids beside the logits), and PR 35 for ``decode`` and the
# pool's three writers (the pool's device format, ``device_shape``);
# ``prefill`` keeps PR 33's; PR 37 for ``decode`` again (a row's token may
# be the id the step before chose: ``last_ids`` and ``src``); PR 41 for
# GPT-2's ``prefill`` and ``decode`` (its head is tied and tiny's 64-wide
# rows are no whole lanes: the serving tree holds ``wte`` a second time,
# padded, and ``_embed`` gathers from that; llama names no table and keeps
# both digests); PR 46 for ``decode`` and the pool's three writers of both
# families, in one label and nothing else: the holder always holds a dict,
# so the result that was ``jax.result_info = "result[0]"`` is
# ``"result[0]['kv']"`` (CHANGES.md has the diff of each text); ``prefill``
# is handed no holder and keeps its text.
PARENT_LOWERINGS = {
    ("gpt2:tiny", "prefill"): "cc6581d8156c0206",
    ("gpt2:tiny", "decode"): "228a438727aaa5d5",
    ("gpt2:tiny", "scatter"): "3480cd9e58132979",
    ("gpt2:tiny", "write_rows"): "89efab135b94e92d",
    ("gpt2:tiny", "load_block"): "7ccb454b3c8064fd",
    ("llama:tiny", "prefill"): "b821c7fcd0890e93",
    ("llama:tiny", "decode"): "66e11f2a99f4cedc",
    ("llama:tiny", "scatter"): "0525f342bdfe481a",
    ("llama:tiny", "write_rows"): "9cf7c8c73ff29605",
    ("llama:tiny", "load_block"): "9a0914875cdb5c7d",
}


@pytest.mark.parametrize("model", ["gpt2:tiny", "llama:tiny"])
def test_stateless_families_lower_byte_for_byte_as_on_the_parent(model):
    import hashlib

    from ray_tpu.serve.llm import kv_cache as kvmod
    from ray_tpu.serve.llm.model_runner import ModelRunner
    runner = ModelRunner(engine_cfg(model=model))
    assert runner.state_spec is None
    S = jax.ShapeDtypeStruct

    def i32(*shape):
        return S(shape, jnp.int32)

    layers, kv_heads, d = runner.n_layer, runner.n_kv, runner.head_dim
    pool = {"kv": S(kvmod.device_shape(64, layers, 8, kv_heads, d),
                    jnp.float32)}
    kv = S((layers, 32, kv_heads, d), jnp.float32)
    one = S((layers, 1, kv_heads, d), jnp.float32)
    programs = kvmod._programs()
    lowered = {
        "prefill": runner._prefill.lower(None, runner.params, i32(1, 32),
                                         i32()),
        "decode": runner._decode.lower(pool, runner.params, i32(4), i32(4),
                                       i32(4, 8), i32(4), i32(), i32(4),
                                       i32(4)),
        "scatter": programs.scatter_prefill.lower(pool, i32(4), kv, kv,
                                                  i32()),
        "write_rows": programs.write_rows.lower(pool, i32(1), i32(1), one,
                                                one),
        "load_block": programs.load_block.lower(
            pool, i32(), S((layers, 2, 8, kv_heads, d), jnp.float32)),
    }
    got = {(model, name): hashlib.sha256(low.as_text().encode())
           .hexdigest()[:16] for name, low in lowered.items()}
    assert got == {k: v for k, v in PARENT_LOWERINGS.items()
                   if k[0] == model}
