"""Ling 3.0: Kimi-delta-attention layers beside latent-attention layers, a
row of per-channel-gated delta-rule state and a latent page in the one
cache manager, the absorbed decode path, group-limited sigmoid routing of
which one group's experts are held.

The program against the plain float32 reference (``perfbench/reference/
ling_ref.py``) at a small size: 4 heads of 8, a latent of 16 + 4 rotary,
pages of 8, chunks of 32 (the rule's 16), 8 experts in 2 groups with 4
held.  The tiny model is float32, so the two agree to what float32
arithmetic in another order leaves.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.families import ling as family
from perfbench.reference import ling_ref as ref
from ray_tpu.models import ling
from ray_tpu.ops import delta_rule as dr
from ray_tpu.ops import moe
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve import llm
from ray_tpu.serve.llm import kv_cache as kvmod

CFG = ling.tiny()
SETTINGS = family.sizes_of_model(CFG)
BS, C = 8, CFG.prefill_chunk
ATOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return ling.init_params(jax.random.key(0), CFG)


def _engine(params=None, **over):
    cfg = llm.EngineConfig(**{**dict(
        model="ling:tiny", block_size=BS, num_blocks=96, max_num_seqs=4,
        max_prefill_tokens=256, max_model_len=256,
        decode_batch_buckets=(4,), prefill_len_buckets=(64, 128, 256),
        share_weights=False), **over})
    return llm.LLMEngine(cfg, params=params, start=False)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 128, n)]


def _run_out(eng, limit=4000):
    for _ in range(limit):
        if not eng.step() and not eng.sched.has_work():
            return
    raise AssertionError("the engine did not finish")


# ------------------------------------------------------ program vs reference
@pytest.mark.parametrize("n", [20, 70])
def test_forward_is_the_references(params, n):
    """The whole forward, chunk by chunk with the state carried, against
    the reference's token-by-token rule and un-absorbed attention, under
    the program's choices and under the reference's own."""
    toks = jnp.asarray([_prompt(n, seed=n)])
    logits, rows, _, _, ids = ling.forward_prefill(params, toks, CFG,
                                                   choices=True)
    assert rows.shape == (1, 1, n, 1, CFG.latent_row)
    want, audit = ref.logits(params, toks, SETTINGS, choices=np.asarray(ids))
    assert float(jnp.abs(want - logits).max()) < ATOL
    assert audit == {"decisions": 3 * n, "differing": 0, "worst_margin": 0.0}
    own = ref.logits(params, toks, SETTINGS)
    assert float(jnp.abs(own - logits).max()) < ATOL


# --------------------------------------------------- the per-channel rule
def _rule_inputs(seed, T, B=2, H=3, dk=16, dv=8, gate=None):
    ks = jax.random.split(jax.random.key(seed), 7)
    q = jax.random.normal(ks[0], (B, T, H, dk)) / 4
    k = dr.l2norm(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
    g = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (B, T, H, dk))) \
        if gate is None else jnp.full((B, T, H, dk), gate)
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, H, dk, dv))


def _recurrence(xs, state):
    def token(s, x):
        o, s = dr.kda_step(s, *x)
        return s, o
    state, o = jax.lax.scan(token, state,
                            tuple(jnp.moveaxis(a, 1, 0) for a in xs))
    return jnp.moveaxis(o, 0, 1), state


@pytest.mark.parametrize("T,chunk,gate", [
    (128, 64, None),        # whole chunks
    (150, 64, None),        # a last chunk that is part of one
    (90, 32, -4.999),       # every channel at the bound: exponents of 75
    (90, 32, -0.001),       # nothing forgotten
    (40, 16, None),         # a chunk of one block
])
def test_the_chunked_rule_is_the_recurrence(T, chunk, gate):
    """``kda_chunks`` from an entering state against ``kda_step`` token by
    token: outputs and the state that leaves."""
    xs, s0 = _rule_inputs(T, T, gate=gate)
    want_o, want_s = _recurrence(xs, s0)
    o, s = dr.kda_chunks(*xs, s0, chunk=chunk)
    assert float(jnp.abs(o - want_o).max()) < 2e-5
    assert float(jnp.abs(s - want_s).max()) < 2e-5


def test_padding_leaves_the_state_as_it_is():
    xs, s0 = _rule_inputs(5, 48)
    q, k, v, g, beta = xs
    real = jnp.arange(48) < 29
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, s = dr.kda_chunks(q, k, v, g, beta, s0, chunk=16)
    _, want = _recurrence(tuple(a[:, :29] for a in xs), s0)
    assert float(jnp.abs(s - want).max()) < 2e-5


def test_a_step_over_rows_is_the_rule_as_written():
    """``kda_step`` on a batch of rows against the four lines written out,
    a row at a time."""
    (q, k, v, g, beta), s0 = _rule_inputs(9, 1, B=5)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    o, s = dr.kda_step(s0, q, k, v, g, beta)
    for b in range(5):
        for h in range(3):
            S = np.exp(np.asarray(g[b, h]))[:, None] * np.asarray(s0[b, h])
            d = float(beta[b, h]) * (np.asarray(v[b, h])
                                     - S.T @ np.asarray(k[b, h]))
            S = S + np.outer(np.asarray(k[b, h]), d)
            assert np.abs(S - np.asarray(s[b, h])).max() < 1e-5
            assert np.abs(S.T @ np.asarray(q[b, h])
                          - np.asarray(o[b, h])).max() < 1e-5


def test_rows_of_a_store_are_stepped_where_they_lie():
    """``kda_step_rows``: the in-place kernel (interpret mode) against the
    gather, ``kda_step`` and the scatter; a batch row that names no row of
    the store writes nowhere and reads nothing of a live row's."""
    (q, k, v, g, beta), _ = _rule_inputs(11, 1, B=5, H=4, dk=128, dv=128)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    store = jax.random.normal(jax.random.key(12), (2, 7, 4, 128, 128))
    rows = jnp.asarray([3, 0, 9, 5, 9], jnp.int32)      # 9: none of the 7
    want_o, want = dr.kda_step_rows(store, 1, rows, q, k, v, g, beta)
    got_o, got = dr._kda_rows_call(store, 1, rows, q, k, v, g, beta,
                                   interpret=True)
    live = np.asarray(rows) < 7
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(got_o - want_o)[live].max()) < 1e-5
    assert np.array_equal(np.asarray(got[0]), np.asarray(store[0]))
    assert np.array_equal(np.asarray(got[1])[[1, 2, 4, 6]],
                          np.asarray(store[1])[[1, 2, 4, 6]])
    assert float(jnp.abs(got[1, 3] - store[1, 3]).max()) > 0.1


def test_conv_tails_of_a_store_are_stepped_where_they_lie():
    """``conv_step_rows``: the in-place kernel (interpret mode) against the
    gather, ``conv_step`` and the scatter, and both against the conv over
    the whole sequence."""
    from ray_tpu.ops import ssm
    ks = jax.random.split(jax.random.key(13), 3)
    store = jax.random.normal(ks[0], (2, 7, 3 * 16, 128))
    rows = jnp.asarray([3, 0, 9, 5, 9], jnp.int32)      # 9: none of the 7
    x, w = jax.random.normal(ks[1], (5, 2048)), jax.random.normal(
        ks[2], (4, 2048))
    want_y, want = ssm.conv_step_rows(store, 1, rows, x, w)
    got_y, got = ssm._conv_rows_call(store, 1, rows, x, w, interpret=True)
    live = np.asarray(rows) < 7
    assert float(jnp.abs(got - want).max()) == 0.0
    assert float(jnp.abs(got_y - want_y)[live].max()) < 1e-5
    assert np.array_equal(np.asarray(got[0]), np.asarray(store[0]))
    assert np.array_equal(np.asarray(got[1])[[1, 2, 4, 6]],
                          np.asarray(store[1])[[1, 2, 4, 6]])
    # row 3's tail was x_{t-3..t-1}: its step is the causal conv's last
    whole = jnp.concatenate([store[1, 3].reshape(3, 2048), x[:1]])[None]
    y, _ = ssm.causal_conv(whole, w, None)
    assert float(jnp.abs(y[0, -1] - got_y[0]).max()) < 1e-5
    assert np.array_equal(np.asarray(got[1, 3]).reshape(3, 2048),
                          np.asarray(whole[0, 1:]))


def test_the_scalar_gated_rule_lowers_as_it_did():
    """The scalar-gated forms are untouched: their traced program names
    nothing of the per-channel gate's."""
    (q, k, v, g, beta), s0 = _rule_inputs(3, 64)
    text = jax.jit(dr.gated_delta_rule).lower(
        q, k, v, g[..., 0], beta).as_text()
    assert "kda" not in text


# ------------------------------------------------------------ latent pages
def _latent_case(seed=0, B=3, H=4, lora=16, rope=4, n_blocks=20):
    rng = np.random.default_rng(seed)
    r, f = lora + rope, 128
    pool = np.zeros((2, 1, n_blocks, BS, f), np.float32)
    pool[..., :r] = rng.normal(0, 1, (2, 1, n_blocks, BS, r))
    tables = rng.permutation(n_blocks)[:18].reshape(B, 6).astype(np.int32)
    lens = np.asarray([0, 13, 48], np.int32)
    q = rng.normal(0, 1, (B, H, r)).astype(np.float32)
    new = rng.normal(0, 1, (B, r)).astype(np.float32)
    return pool, tables, lens, q, new, lora


def test_absorbed_attention_is_the_unabsorbed():
    """Scores and values through W_kvb folded into the query and out of
    the result, over paged latent rows, against keys and values
    up-projected a position at a time."""
    pool, tables, lens, _, new, lora = _latent_case()
    rng = np.random.default_rng(1)
    B, H, nope, rope, vd = 3, 4, 8, 4, 8
    w_kvb = rng.normal(0, 0.3, (lora, H, nope + vd)).astype(np.float32)
    q_n = rng.normal(0, 1, (B, H, nope)).astype(np.float32)
    q_r = rng.normal(0, 1, (B, H, rope)).astype(np.float32)
    scale = 1 / np.sqrt(nope + rope)
    q_abs = np.concatenate(
        [np.einsum("bhn,chn->bhc", q_n, w_kvb[..., :nope]), q_r], -1) * scale
    o_c = pa.latent_attention_decode(jnp.asarray(q_abs), jnp.asarray(pool), 1,
                                     jnp.asarray(tables), jnp.asarray(lens),
                                     jnp.asarray(new), lora)
    got = np.einsum("bhc,chv->bhv", np.asarray(o_c), w_kvb[..., nope:])
    for b in range(B):
        rows = np.concatenate(
            [pool[1, 0, tables[b]].reshape(-1, 128)[:lens[b], :lora + rope],
             new[b:b + 1]])
        kv = np.einsum("tc,chd->thd", rows[:, :lora], w_kvb)
        s = (np.einsum("hn,thn->ht", q_n[b], kv[..., :nope])
             + q_r[b] @ rows[:, lora:].T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("ht,thv->hv", p, kv[..., nope:])
        assert np.abs(got[b] - want).max() < 1e-4


def test_the_latent_kernel_is_the_gather():
    pool, tables, lens, q, new, lora = _latent_case(seed=2)
    pad = lambda x: jnp.pad(jnp.asarray(x),            # noqa: E731
                            [(0, 0)] * (x.ndim - 1) + [(0, 128 - x.shape[-1])])
    args = (pad(q), jnp.asarray(pool), 1, jnp.asarray(tables),
            jnp.asarray(lens), pad(new), lora)
    want = pa._latent_decode_gather(*args)
    got = pa._latent_decode_kernel(*args, interpret=True)
    assert got.shape == (3, 4, lora)
    assert float(jnp.abs(got - want).max()) < 1e-5


def _cache(**over):
    return kvmod.PagedKVCache(**{**dict(
        num_blocks=12, n_layer=0, block_size=BS, n_kv=4, head_dim=8,
        state=ling.recurrent_state(CFG), max_seqs=3, state_layers=3,
        latent_layers=2, latent_dim=20), **over})


def test_the_cache_keeps_latent_pages_under_the_one_table():
    """One plane, the table's own blocks; a prompt's rows, a token's row,
    and nothing of another sequence's."""
    cache = _cache()
    held = cache.pool.abstract()
    assert held["latent"].shape == kvmod.device_shape(12, 2, BS, 1, 20,
                                                      planes=1) \
        == (2, 1, 12, BS, 128)
    assert held["kv"].shape[0] == 0 and cache.latent_bytes == 2 * 12 * BS * 512
    rng = np.random.default_rng(0)
    rows = {s: rng.normal(0, 1, (2, 24, 1, 20)).astype(np.float32)
            for s in "ab"}
    for s, n in (("a", 19), ("b", 11)):
        cache.alloc_seq(s, n)
        cache.scatter_prefill(s, rows[s], rows[s], n)
    blk, off, grew = cache.append_slot("b")
    assert (off, grew) == (3, False)
    token = rng.normal(0, 1, (2, 1, 20)).astype(np.float32)
    cache.write_token(blk, off, token, token)
    pages = cache.latent_blocks()                    # (N, L, bs, 20)
    for s, n in (("a", 19), ("b", 11)):
        flat = pages[cache.table(s)].transpose(1, 0, 2, 3).reshape(2, -1, 20)
        assert np.array_equal(flat[:, :n], rows[s][:, :n, 0])
    assert np.array_equal(pages[blk][:, off], token[:, 0])
    untouched = [b for b in range(12)
                 if b not in cache.table("a") + cache.table("b")]
    assert not pages[untouched].any()
    assert cache.free_seq("a") == 3 and cache.free_seq("b") == 2
    assert cache.free_block_count() == 12 and cache.state_rows_used() == 0


def test_latent_pages_are_neither_forked_nor_exported():
    cache = _cache(state=None, max_seqs=0, state_layers=None)
    cache.alloc_seq("s", 10)
    with pytest.raises(NotImplementedError, match="latent"):
        cache.fork_seq("s", "t")
    with pytest.raises(NotImplementedError, match="latent"):
        cache.block_bytes(0)
    with pytest.raises(NotImplementedError, match="latent"):
        cache.load_block(0, b"")
    with pytest.raises(ValueError, match="latent pages beside K/V"):
        _cache(n_layer=1)


# ------------------------------------------------------------- the engine
@pytest.mark.parametrize("n,steps", [(77, 40), (30, 20)])
def test_prefill_in_chunks_then_stateful_paged_decode_is_the_references(
        params, n, steps):
    """A prompt through the chunked prefill (state carried from chunk to
    chunk in the store's staging row, latent rows in the staging), its rows
    scattered into latent pages and its state committed to its row, then
    decode steps through state rows and latent pages: every step's logits
    against the reference's full forward under the program's choices."""
    eng = _engine(params)
    runner, cache = eng.runner, eng.cache
    try:
        prompt = _prompt(n, seed=n)
        cache.alloc_seq("s", n)
        logits, ks, vs = runner.prefill(prompt)
        chose = [np.asarray(runner.choices)[:, :n]]
        cache.scatter_prefill("s", np.asarray(ks, np.float32),
                              np.asarray(vs, np.float32), n)
        got, seq = [logits], list(prompt)
        maxb = eng.cfg.max_blocks_per_seq
        for _ in range(steps):
            seq.append(int(np.argmax(got[-1])))
            cache.append_slot("s")
            tables = np.zeros((1, maxb), np.int32)
            table = cache.table("s")
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, ks, vs = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                       cache.pool, tables, at)
            assert ks.shape == (1, 4, 1, CFG.latent_row)
            chose.append(np.asarray(runner.choices)[:, :1])
            got.append(lg[0])
        want, audit = ref.logits(params, [seq], SETTINGS,
                                 choices=np.concatenate(chose, axis=1))
        want = np.asarray(want)[0]
        diffs = [float(np.abs(g - want[n - 1 + i]).max())
                 for i, g in enumerate(got)]
        assert max(diffs) < ATOL, diffs
        assert audit["worst_margin"] < 1e-5
        cache.free_seq("s")
        assert cache.free_block_count() == cache.num_blocks
    finally:
        eng.shutdown()


def test_the_engines_loop_keeps_sequences_rows_and_pages_apart(params):
    """Through submit and the loop: prompts of one chunk and of several in
    one queue, chunks between decode steps, one step in flight, every
    sequence its own row of state and its own pages; each request's tokens
    are the greedy continuation the reference gives it ALONE."""
    eng = _engine(params)
    try:
        prompts = [_prompt(n, seed=n) for n in (20, 70, 130, 45)]
        streams = [eng.submit(p, llm.SamplingParams(max_tokens=12))
                   for p in prompts]
        _run_out(eng)
        for prompt, stream in zip(prompts, streams):
            out = stream.tokens()
            assert len(out) == 12
            want = np.asarray(ref.logits(params, [prompt + out[:-1]],
                                         SETTINGS))[0]
            assert out == [int(t) for t in
                           want[len(prompt) - 1:].argmax(-1)]
        stats = eng.stats()
        assert stats["preemptions"] == 0
        assert (stats["latent_layers"], stats["state_layers"],
                stats["kv_layers"]) == (1, 3, 0)
        assert stats["latent_pages_read"] > 0
        assert stats["latent_bytes"] == 96 * BS * 512
        assert stats["blocks_free"] == eng.cfg.num_blocks
        assert stats["state_rows_used"] == 0
        assert stats["prefill_chunks"] == sum(-(-len(p) // C)
                                              for p in prompts)
        assert eng.runner.route_spec == {"layers": 3, "k": 2, "held": (0, 4)}
        assert 0 <= stats["experts_touched"] \
            <= 4 * stats["routed_layer_steps"]
    finally:
        eng.shutdown()


def test_a_preempted_sequence_is_prefilled_again_and_goes_on(params):
    """Under cache pressure the latest arrival is evicted: its pages and
    its row go back, it runs its chunks again (no snapshot of state), and
    every request's tokens are what they are without pressure."""
    prompts = [_prompt(n, seed=n) for n in (90, 100, 110)]

    def served(num_blocks):
        eng = _engine(params, num_blocks=num_blocks, max_num_seqs=3)
        try:
            streams = [eng.submit(p, llm.SamplingParams(max_tokens=40))
                       for p in prompts]
            _run_out(eng)
            return [s.tokens() for s in streams], eng.stats()
        finally:
            eng.shutdown()

    roomy, stats = served(96)
    assert stats["preemptions"] == 0
    tight, stats = served(50)
    assert stats["preemptions"] > 0
    assert tight == roomy
    assert stats["blocks_free"] == 50 and stats["state_rows_used"] == 0


@pytest.mark.parametrize("call", ["prefill_remote", "attach", "fork_seq"])
def test_what_moves_one_tables_blocks_refuses_the_family(params, call):
    eng = _engine(params)
    try:
        with pytest.raises(NotImplementedError,
                           match="recurrent state|latent"):
            if call == "prefill_remote":
                eng.prefill_remote(_prompt(20))
            elif call == "attach":
                eng.attach({"model": "ling:tiny"})
            else:
                eng.cache.alloc_seq("s", 10)
                eng.cache.fork_seq("s", "t")
    finally:
        eng.shutdown()


# ---------------------------------------------------------------- the router
def _route_inputs(n=64, experts=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (n, 24))
    # as a norm leaves it: the reference's router norms its input
    return (x / jnp.sqrt((x * x).mean(-1, keepdims=True)),
            jax.random.normal(ks[1], (24, experts)) / 4,
            jax.random.normal(ks[2], (experts,)) * 0.05)


@pytest.mark.parametrize("n_group,topk_group", [(4, 2), (8, 4), (2, 1)])
def test_the_group_limited_choice_is_a_direct_top_k(n_group, topk_group):
    """``route_sigmoid`` under a group limit against the rule written out a
    token at a time in numpy; the reference's own choice is the same."""
    x, w, bias = _route_inputs()
    idx, weights = moe.route_sigmoid(x, w, bias, 3, 2.5, n_group=n_group,
                                     topk_group=topk_group)
    s = 1 / (1 + np.exp(-np.asarray(x @ w, np.float64)))
    sel = s + np.asarray(bias)
    size = 32 // n_group
    for t in range(x.shape[0]):
        score = [np.sort(sel[t, g * size:(g + 1) * size])[-2:].sum()
                 for g in range(n_group)]
        groups = np.argsort(score)[-topk_group:]
        allowed = [e for g in groups for e in range(g * size, (g + 1) * size)]
        want = sorted(allowed, key=lambda e: -sel[t, e])[:3]
        assert sorted(want) == sorted(int(e) for e in idx[t])
        assert np.allclose(np.asarray(weights[t]),
                           2.5 * s[t, idx[t]] / s[t, idx[t]].sum(), atol=1e-5)
    _, gates, differs, margin = ref._route(
        x, jnp.ones(24), w, bias, np.asarray(idx), k=3, eps=0.0, scale=2.5,
        n_group=n_group, topk_group=topk_group)
    assert not differs.any() and float(margin.max()) <= 1e-6


def test_one_group_routes_bit_for_bit_as_before():
    """``n_group`` 1 is the ungrouped choice: the same ids and weights to
    the bit as the top-k over score + bias written here as it stood, and a
    traced program without a second top-k."""
    x, w, bias = _route_inputs(seed=1)

    def before(x, w, bias):
        scores = jax.nn.sigmoid(jnp.dot(x, w.astype(x.dtype),
                                        preferred_element_type=jnp.float32))
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), 3)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * 2.5

    now = lambda x, w, bias: moe.route_sigmoid(x, w, bias, 3, 2.5)  # noqa: E731
    for got, want in zip(now(x, w, bias), before(x, w, bias)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    text = str(jax.make_jaxpr(now)(x, w, bias))
    assert text.count("top_k") == 1 and "scatter" not in text


def test_the_audit_names_a_choice_outside_the_best_groups():
    """A program that took an expert of a group the reference did not keep
    is a differing decision with a margin as large as the groups lie
    apart."""
    x, w, bias = _route_inputs(seed=2)
    own, _ = moe.route_sigmoid(x, w, bias, 3, 2.5, n_group=4, topk_group=2)
    sel = np.asarray(jax.nn.sigmoid(x @ w) + bias)
    wrong = np.asarray(own).copy()
    for t in range(len(wrong)):                 # the worst group's best
        score = [np.sort(sel[t, g * 8:(g + 1) * 8])[-2:].sum()
                 for g in range(4)]
        g = int(np.argmin(score))
        wrong[t, 0] = g * 8 + int(np.argmax(sel[t, g * 8:(g + 1) * 8]))
    _, _, differs, margin = ref._route(
        x, jnp.ones(24), w, bias, wrong, k=3, eps=0.0, scale=2.5,
        n_group=4, topk_group=2)
    assert differs.all() and float(margin.min()) > 0


# ----------------------------------------------------------------- the share
@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        held):
    """Every first_held of the small size: the shares' routed parts summed,
    and the shared expert once, are the uncut layer's F, in the program and
    in the reference alike; a share alone is not."""
    whole = dataclasses.replace(CFG, held_experts=0)
    full = ling.init_params(jax.random.key(3), whole)
    lp = full["layers"]["l01"]
    h = jax.random.normal(jax.random.key(4), (37, CFG.n_embd))
    h = h / jnp.sqrt((h * h).mean(-1, keepdims=True))   # as a norm leaves it
    want, ids = ling._ffn(h, lp, whole)
    shared = ling._swiglu(h, lp["shared"], whole)
    lp32 = ref._widened({k: v for k, v in lp.items() if k != "experts"})
    _, gates, _, _ = ref._route(
        h, jnp.ones(CFG.n_embd), lp32["router"]["kernel"],
        lp32["expert_bias"], None, k=2, eps=0.0, scale=CFG.route_scale,
        n_group=CFG.n_group, topk_group=CFG.topk_group)
    total, ref_total = jnp.zeros_like(want), jnp.zeros_like(want)
    for first in range(0, 8, held):
        share = dataclasses.replace(CFG, held_experts=held, first_held=first)
        mine = {**lp, "experts": {k: w[first:first + held]
                                  for k, w in lp["experts"].items()}}
        got, share_ids = ling._ffn(h, mine, share)
        assert np.array_equal(share_ids, ids)    # the router is whole
        total = total + got - shared
        ref_total = ref_total + ref.routed_part(h, gates, mine["experts"],
                                                first)
        if held < 8:
            assert float(jnp.abs(got - want).max()) > 1e-2
    assert float(jnp.abs(total + shared - want).max()) < 1e-4
    assert float(jnp.abs(ref_total + ref._swiglu(h, lp32["shared"])
                         - want).max()) < 1e-4
