"""AFMoE (Trinity): sliding-window and full attention mixed, pages of two
kinds in the one cache manager, a gated attention output, sigmoid-routed
experts of which a share is held.

The program against the plain float32 reference (``perfbench/reference/
afmoe_ref.py``) at a small size: a window of 48 positions, pages of 8,
chunks of 32, 8 experts with 4 held, contexts several windows long.  The
tiny model is float32, so the two agree to what float32 arithmetic in
another order leaves.

A needle.  At random weights a softmax over a window of keys is near flat
and a window off by a page moves a logit by less than a rounding, so the
window's edge is held by contrived keys: one key whose score is far above
the rest, with a value far from the rest, must change nothing just outside
the window of a sliding layer and be all the output just inside it, and be
seen by a full layer either way.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.families import afmoe as family
from perfbench.reference import afmoe_ref as ref
from ray_tpu.models import afmoe
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import window_attention as wa
from ray_tpu.serve import llm
from ray_tpu.serve.llm import kv_cache as kvmod

CFG = afmoe.tiny()
SETTINGS = family.sizes_of_model(CFG)
W, BS, C = CFG.sliding_window, 8, CFG.prefill_chunk
ATOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return afmoe.init_params(jax.random.key(0), CFG)


def _engine(params=None, **over):
    cfg = llm.EngineConfig(**{**dict(
        model="afmoe:tiny", block_size=BS, num_blocks=96, max_num_seqs=4,
        max_prefill_tokens=256, max_model_len=256,
        decode_batch_buckets=(4,), prefill_len_buckets=(64, 128, 256),
        share_weights=False), **over})
    return llm.LLMEngine(cfg, params=params, start=False)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 128, n)]


def _run_out(eng, limit=2000):
    for _ in range(limit):
        if not eng.step() and not eng.sched.has_work():
            return
    raise AssertionError("the engine did not finish")


# ------------------------------------------------------ program vs reference
@pytest.mark.parametrize("n", [20, 48, 49, 150])
def test_forward_is_the_references(params, n):
    """Under the window, at its edge, one past it, three windows long (the
    ring of the sliding layers turns twice)."""
    toks = jnp.asarray([_prompt(n, seed=n)])
    logits, ks, vs, state, ids = afmoe.forward_prefill(params, toks, CFG,
                                                       choices=True)
    assert logits.shape == (1, n, CFG.vocab_size) and state is None
    assert ks.shape == vs.shape == (5, 1, n, 2, 8)
    assert ids.shape == (4, n, 2) and ids.dtype == jnp.int32
    want, audit = ref.logits(params, toks, SETTINGS, choices=ids)
    assert float(jnp.abs(want - logits).max()) < ATOL
    assert audit["differing"] <= 0.01 * audit["decisions"]
    assert audit["worst_margin"] < 1e-5
    assert float(jnp.abs(afmoe.forward(params, toks, CFG) - logits).max()) \
        == 0.0


def test_the_window_is_part_of_the_result(params):
    """The same tokens under another window give other logits past it and
    the same logits under it: the mask is not a no-op."""
    toks = jnp.asarray([_prompt(100)])
    narrow = dataclasses.replace(CFG, sliding_window=24)
    a = afmoe.forward(params, toks, CFG)
    b = afmoe.forward(params, toks, narrow)
    assert float(jnp.abs(a - b)[0, :24].max()) < 1e-5
    assert float(jnp.abs(a - b)[0, 60:].max()) > 1e-2
    want = ref.logits(params, toks, {**SETTINGS, "sliding_window": 24})
    assert float(jnp.abs(want - b).max()) < ATOL


def test_the_full_layers_have_no_rotary_embedding(params):
    """A full layer's cached keys are the normed keys as they are; a
    sliding layer's are rotated by their position."""
    toks = jnp.asarray([_prompt(40)])
    _, ks, _, _ = afmoe.forward_prefill(params, toks, CFG)
    shifted = jnp.concatenate([toks[:, :1], toks], axis=1)
    _, ks2, _, _ = afmoe.forward_prefill(params, shifted, CFG)
    # kind order: the full layer first.  Layer 0's input is the embedding:
    # its keys at a position depend on the token alone (sliding, rotated)
    assert float(jnp.abs(ks[1, 0, 5] - ks2[1, 0, 6]).max()) > 1e-3
    lp = params["layers"]["l00"]
    x = afmoe._embed(params, toks[0], CFG)
    u = afmoe._rms_norm(x, lp["norm1"]["scale"], CFG.rms_eps)
    _, k_full, _ = afmoe._heads(u, jnp.arange(40), lp, CFG, afmoe.FULL)
    _, k_slid, _ = afmoe._heads(u, jnp.arange(40), lp, CFG, afmoe.SLIDING)
    assert float(jnp.abs(k_slid[0] - k_full[0]).max()) == 0.0   # position 0
    assert float(jnp.abs(k_slid[7] - k_full[7]).max()) > 1e-3
    assert float(jnp.abs(ks[1, 0] - k_slid).max()) < 1e-6


@pytest.mark.parametrize("n,steps", [(77, 60), (30, 25), (96, 150)])
def test_prefill_in_chunks_then_paged_decode_is_the_references(params, n,
                                                               steps):
    """A prompt through the chunked prefill (its chunks cross the window's
    edge), its K/V scattered into pages of two kinds, then decode steps
    through both pools while window blocks go back: every step's logits
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
            blk, off, _ = cache.append_slot("s")
            tables = np.zeros((1, maxb), np.int32)
            table = cache.table("s")
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, ks, vs = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                       cache.pool, tables, at)
            chose.append(np.asarray(runner.choices)[:, :1])
            got.append(lg[0])
            # a window layer never holds more than ceil(W / bs) + 1 blocks
            wtable = cache.window_table("s")
            held = [b for b in wtable if b != cache.window_blocks]
            assert len(held) <= kvmod.window_columns(W, BS)
            assert len(wtable) == len(table)
        want, audit = ref.logits(params, [seq], SETTINGS,
                                 choices=np.concatenate(chose, axis=1))
        want = np.asarray(want)[0]
        diffs = [float(np.abs(g - want[n - 1 + i]).max())
                 for i, g in enumerate(got)]
        assert max(diffs) < ATOL, diffs
        assert audit["worst_margin"] < 1e-5
        if n + steps > W + BS:
            assert cache.window_counts()[1] > 0      # blocks went back
        cache.free_seq("s")
        assert cache.window_counts() == (0, cache.window_counts()[1], 0)
        assert cache.free_block_count() == cache.num_blocks
    finally:
        eng.shutdown()


def test_the_engines_loop_serves_mixed_lengths_greedily(params):
    """Through submit and the loop: prompts under and over the window in
    one queue, chunks between decode steps, one step in flight; each
    request's tokens are the greedy continuation the reference gives."""
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
            greedy = [int(t) for t in want[len(prompt) - 1:].argmax(-1)]
            assert out == greedy
        stats = eng.stats()
        assert stats["preemptions"] == 0 and stats["window_layers"] == 4
        assert stats["window_blocks"]["held"] == 0
        assert stats["window_blocks"]["released"] > 0
        assert 0 < stats["window_blocks_read"] \
            < stats["window_blocks_unwindowed"]
        assert stats["blocks_free"] == eng.cfg.num_blocks
        assert stats["prefill_chunks"] == sum(-(-len(p) // C)
                                              for p in prompts)
    finally:
        eng.shutdown()


# ------------------------------------------------------------------ a needle
def _needle_pool(rng, n_blocks, kv, d, table, pos, u):
    pool = rng.normal(0, 0.1, (1, 2, n_blocks, BS, kv * d)).astype(np.float32)
    blk, off = table[pos // BS], pos % BS
    pool[0, 0, blk, off] = (34.0 / np.sqrt(d) * u).reshape(-1)    # score ~34
    pool[0, 1, blk, off] = 100.0
    return pool


@pytest.mark.parametrize("ctx", [
    pytest.param(61, id="no multiple of the block"),
    pytest.param(W + 5 * BS - 1, id="the first step after a block went back"),
    pytest.param(W - 1, id="the window exactly full"),
    pytest.param(3 * W + 3, id="three windows long")])
@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_a_decode_needle_at_the_windows_edge(ctx, impl):
    """One key far above the rest: just outside the window it changes
    nothing in a sliding layer and everything in a full one; just inside it
    is all the output.  The columns behind the window name no block."""
    h, kv, d = 6, 2, 128 if impl == "kernel" else 8
    rng = np.random.default_rng(ctx)
    maxb = -(-4 * W // BS)
    n_blocks = maxb + 3
    lo = max(ctx - (W - 1), 0)

    def attend(*args, window=None):
        if impl == "gather":
            return pa._paged_decode_gather(*args, window=window)
        return pa._paged_decode_kernel(*args, window=window, interpret=True)

    for where, pos in (("outside", lo - 1), ("inside", lo)):
        if pos < 0:
            continue
        table = rng.permutation(n_blocks)[:maxb].astype(np.int32)
        u = rng.normal(0, 1, (kv, d)).astype(np.float32)
        u *= np.sqrt(d) / np.linalg.norm(u, axis=-1, keepdims=True)
        pool = _needle_pool(rng, n_blocks, kv, d, table, pos, u)
        given = table.copy()
        given[:lo // BS] = n_blocks                 # given back
        q = jnp.asarray(np.repeat(u, h // kv, axis=0)[None])
        rest = (jnp.asarray([ctx], jnp.int32), jnp.zeros((1, kv, d)),
                jnp.zeros((1, kv, d)))
        out = np.asarray(attend(q, jnp.asarray(pool), 0,
                                jnp.asarray(given[None]), *rest, window=W))
        full = np.asarray(attend(q, jnp.asarray(pool), 0,
                                 jnp.asarray(table[None]), *rest))
        assert full.min() > 99.0, (where, full.min())
        if where == "outside":
            assert np.abs(out).max() < 1.0, np.abs(out).max()
        else:
            assert out.min() > 99.0, out.min()


@pytest.mark.parametrize("impl", ["plain", "kernel"])
def test_a_prefill_needle_at_the_windows_edge(impl):
    """A chunk whose queries straddle the needle's edge: the queries up to
    needle + window - 1 see it, none past them; without a window all do."""
    kv, rep, d = 2, 3, 128 if impl == "kernel" else 8
    chunk = 256 if impl == "kernel" else C
    window = 300 if impl == "kernel" else W
    rng = np.random.default_rng(3)
    n = wa.ring_segments(window, chunk)
    index = 4
    needle = index * chunk - window + chunk // 2     # seen by half the chunk
    chunk_of = wa.ring_chunks(jnp.int32(index), n)
    k_all = rng.normal(0, 0.1, (n * chunk, kv * d)).astype(np.float32)
    v_all = rng.normal(0, 0.1, (n * chunk, kv * d)).astype(np.float32)
    u = rng.normal(0, 1, (kv, d)).astype(np.float32)
    u *= np.sqrt(d) / np.linalg.norm(u, axis=-1, keepdims=True)
    q = jnp.asarray(np.broadcast_to(u[None, :, None, :],
                                    (chunk, kv, rep, d)))
    at = (needle // chunk) % n * chunk + needle % chunk
    k_all[at], v_all[at] = (34.0 / np.sqrt(d) * u).reshape(-1), 100.0

    def attend(window):
        args = (q, jnp.asarray(k_all), jnp.asarray(v_all), index * chunk,
                chunk_of, window)
        if impl == "plain":
            return np.asarray(wa._plain(*args))
        return np.asarray(wa._band_flash(*args, interpret=True))

    out = attend(window)
    last = needle + window - 1 - index * chunk       # the last that sees it
    assert 0 < last < chunk - 1
    assert out[:last + 1].min() > 99.0
    assert np.abs(out[last + 1:]).max() < 1.0
    assert attend(None).min() > 99.0


@pytest.mark.parametrize("index", [0, 1, 2, 5])
@pytest.mark.parametrize("window", [None, 300])
def test_the_chunk_kernel_is_the_plain_attention(index, window):
    """The Pallas kernel (interpreted) against plain jax.numpy over a ring
    that has turned ``index`` times and over a full staging; the tiles it
    skips are the tiles no query's band crosses."""
    chunk, kv, rep, d = 256, 2, 3, 128
    key = jax.random.fold_in(jax.random.key(1), index)
    segments = wa.ring_segments(window, chunk) if window else 6
    chunk_of = wa.ring_chunks(jnp.int32(index), segments) if window \
        else jnp.arange(segments, dtype=jnp.int32)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (chunk, kv, rep, d))
    k_all = jax.random.normal(ks[1], (segments * chunk, kv * d))
    v_all = jax.random.normal(ks[2], (segments * chunk, kv * d))
    a = wa._plain(q, k_all, v_all, index * chunk, chunk_of, window)
    b = wa._band_flash(q, k_all, v_all, index * chunk, chunk_of, window,
                       interpret=True)
    assert float(jnp.abs(a - b).max()) < 1e-5
    pos, live, fetch = wa.band_tiles(jnp.int32(index * chunk), chunk_of,
                                     chunk, segments * chunk, window, 128,
                                     256)
    live, fetch, pos = map(np.asarray, (live, fetch, pos))
    for i in range(live.shape[0]):
        first, last = index * chunk + i * 128, index * chunk + i * 128 + 127
        for j in range(live.shape[1]):
            crossed = pos[j] <= last and (
                window is None or pos[j] + 255 > first - window)
            assert bool(live[i, j]) == bool(crossed)
            assert live[i, fetch[i, j]]            # a copy is of a live tile
            if live[i, j]:
                assert fetch[i, j] == j


def test_the_ring_holds_the_chunks_a_window_reaches():
    """Segment s holds the newest chunk that is s modulo the ring, and the
    chunk a new one overwrites lies wholly behind every window of it."""
    for window, chunk in ((48, 32), (4096, 2048), (100, 100), (10, 64)):
        n = wa.ring_segments(window, chunk)
        for index in range(9):
            held = np.asarray(wa.ring_chunks(jnp.int32(index), n))
            assert sorted(c for c in held if c >= 0) \
                == list(range(max(0, index - n + 1), index + 1))
            assert all(c % n == s for s, c in enumerate(held) if c >= 0)
            # the oldest position a query of chunk `index` sees
            oldest = index * chunk - window + 1
            assert oldest >= (index - n + 1) * chunk or index < n - 1


# ----------------------------------------------------------------- the cache
def _cache(**over):
    return kvmod.PagedKVCache(**{**dict(
        num_blocks=40, n_layer=1, block_size=BS, n_kv=2, head_dim=8,
        max_seqs=3, window_layers=4, window=W), **over})


def test_the_cache_keeps_two_pools_and_two_tables():
    cache = _cache()
    held = cache.pool.abstract()
    assert set(held) == {"kv", "kvw"}
    assert held["kv"].shape == kvmod.device_shape(40, 1, BS, 2, 8)
    assert cache.window_blocks == 3 * kvmod.window_columns(W, BS) == 21
    assert held["kvw"].shape == kvmod.device_shape(21, 4, BS, 2, 8)
    assert cache.window_bytes == 4 * 2 * 21 * BS * 128 * 4
    with pytest.raises(ValueError, match="max_seqs"):
        _cache(max_seqs=0)


def test_a_prompts_window_blocks_are_those_its_first_step_sees():
    cache = _cache()
    for n in (1, 8, 47, 48, 49, 55, 56, 100, 163):
        cache.alloc_seq("s", n)
        table, wtable = cache.table("s"), cache.window_table("s")
        first = max(0, n - W + 1) // BS
        assert len(wtable) == len(table) == -(-n // BS)
        assert wtable[:first] == [cache.window_blocks] * first
        assert all(b < cache.window_blocks for b in wtable[first:])
        assert len(wtable) - first <= kvmod.window_columns(W, BS)
        assert cache.window_run(n) == (first * BS, 7 * BS)
        assert cache.free_seq("s") == len(table)
        assert cache.window_counts()[0] == 0


def test_blocks_are_held_by_kind_through_decode_preemption_and_free():
    """Three windows of decode steps on three sequences: the full table
    grows by a block every ``bs`` steps, the window table holds at most
    ceil(W / bs) + 1; a rollback undoes a growth in both; a freed sequence
    returns both kinds; no block is ever in two hands or freed twice."""
    cache = _cache(num_blocks=80)
    most = kvmod.window_columns(W, BS)

    def consistent():
        with cache._lock:
            held = [b for t in cache._wtables.values() for b in t
                    if b != cache.window_blocks]
            assert len(held) == len(set(held))
            assert sorted(held + cache._wfree) == list(range(21))
            full = [b for t in cache._tables.values() for b in t]
            assert sorted(full + cache._free) == list(range(80))

    for sid, n in (("a", 5), ("b", 60), ("c", 100)):
        cache.alloc_seq(sid, n)
    released = 0
    for step in range(3 * W):
        for sid in ("a", "b", "c"):
            if sid == "b" and step == 70:
                continue                    # preempted below
            if not cache.has_seq(sid):
                continue
            before = cache.window_table(sid)
            blk, off, grew = cache.append_slot(sid)
            table, wtable = cache.table(sid), cache.window_table(sid)
            fill = cache.fill(sid)
            assert len(table) == len(wtable) == -(-fill // BS)
            assert grew == (len(wtable) == len(before) + 1)
            live = [b for b in wtable if b != cache.window_blocks]
            assert len(live) <= most
            # what the token at fill - 1 sees is held
            lo = max(fill - 1 - (W - 1), 0) // BS
            assert all(b != cache.window_blocks for b in wtable[lo:])
            assert all(b == cache.window_blocks for b in wtable[:lo])
            if step % 17 == 3:
                cache.rollback_slot(sid, grew)
                assert cache.fill(sid) == fill - 1
                assert len(cache.window_table(sid)) == len(wtable) - grew
                assert cache.append_slot(sid)[:2] == (blk, off)
        if step == 70:
            assert cache.free_seq("b") > 0          # a preemption
            cache.alloc_seq("b", 60 + 70)           # and its re-prefill
        consistent()
        assert cache.window_counts()[1] >= released
        released = cache.window_counts()[1]
    held, released, unwindowed = cache.window_counts()
    assert held <= 3 * most and released > 3 * (3 * W // BS - most)
    assert unwindowed == sum(len(cache.table(s)) for s in "abc")
    with pytest.raises(NotImplementedError, match="window"):
        cache.fork_seq("a", "d")
    for sid in "abc":
        cache.free_seq(sid)
    assert cache.free_seq("a") == 0                 # freed once
    consistent()
    assert cache.window_counts()[0] == 0
    assert cache.free_block_count() == 80


def test_the_window_pool_is_sized_for_the_slots_and_says_so():
    cache = _cache(num_blocks=80)
    for sid in "abc":
        cache.alloc_seq(sid, 3 * W)
    with pytest.raises(kvmod.NoFreeBlocks, match="window"):
        cache.alloc_seq("d", 3 * W)
    assert not cache.has_seq("d")
    assert cache.free_block_count() == 80 - 3 * (3 * W // BS)


def test_the_scatter_writes_each_kind_where_its_table_says():
    """A prompt's packed K/V (the full layer's rows, then each window
    layer's run) lands in the right pool at the right block and offset, and
    positions behind the run land nowhere."""
    cache = _cache()
    n, tb = 77, 128
    cache.alloc_seq("s", n)
    first, run = cache.window_run(n)
    assert (first, run) == (24, 56)
    rng = np.random.default_rng(0)
    full = rng.normal(size=(1, tb, 2, 8)).astype(np.float32)
    band = rng.normal(size=(4, run, 2, 8)).astype(np.float32)
    packed = np.concatenate([full.reshape(1, -1, 2, 8),
                             band.reshape(1, -1, 2, 8)], axis=1)
    cache.scatter_prefill("s", packed, 2 * packed, n)
    blocks, wblocks = cache.blocks(), cache.window_pool_blocks()
    table, wtable = cache.table("s"), cache.window_table("s")
    for t in range(n):
        got = blocks[table[t // BS], 0, :, t % BS]            # (2, KV, D)
        assert np.array_equal(got[0], full[0, t])
        assert np.array_equal(got[1], 2 * full[0, t])
    for t in range(first, n):
        got = wblocks[wtable[t // BS], :, :, t % BS]          # (4, 2, KV, D)
        assert np.array_equal(got[:, 0], band[:, t - first])
    # nothing else was written: the rest of both pools is zero
    assert np.count_nonzero(blocks) == 2 * n * 16
    assert np.count_nonzero(wblocks) == 2 * 4 * (n - first) * 16
    # write_token finds the window block of the same column
    blk, off, _ = cache.append_slot("s")
    k = rng.normal(size=(5, 2, 8)).astype(np.float32)
    cache.write_token(blk, off, k, -k)
    assert np.array_equal(cache.blocks()[blk, 0, 0, off], k[0])
    wblk = cache.window_table("s")[n // BS]
    assert np.array_equal(cache.window_pool_blocks()[wblk, :, 1, off], -k[1:])


@pytest.mark.parametrize("model,held", [
    ("gpt2:tiny", {"kv"}), ("falcon_h1:tiny", {"kv", "state"}),
    ("lfm2:tiny", {"kv", "state"}),
    ("minicpm_sala:tiny", {"kv", "state", "sel"})])
def test_the_other_families_caches_are_as_they_were(model, held):
    """No second pool, no second list, the same holder, and fork as
    before."""
    over = dict(block_size=8, num_blocks=64, max_model_len=128,
                max_prefill_tokens=128, prefill_len_buckets=(32, 64, 128),
                decode_batch_buckets=(4,), max_num_seqs=4) \
        if model.startswith("minicpm") else {}
    eng = llm.LLMEngine(llm.EngineConfig(model=model, share_weights=False,
                                         **over), start=False)
    try:
        cache = eng.cache
        assert set(cache.pool.abstract()) == held
        assert (cache.window_layers, cache.window_blocks,
                cache.window_bytes) == (0, 0, 0)
        assert cache._wfree == [] and eng.runner.window_layers == 0
        cache.alloc_seq("s", 20)
        assert cache._wtables == {}
        cache.append_slot("s")
        cache.free_seq("s")
        assert cache.window_counts() == (0, 0, 0)
        assert eng.stats()["window_layers"] == 0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("call", ["prefill_remote", "attach", "fork_seq"])
def test_what_moves_one_tables_blocks_refuses_the_family(params, call):
    eng = _engine(params)
    try:
        with pytest.raises(NotImplementedError, match="window"):
            if call == "prefill_remote":
                eng.prefill_remote(_prompt(20))
            elif call == "attach":
                eng.attach({"model": "afmoe:tiny"})
            else:
                eng.cache.alloc_seq("s", 10)
                eng.cache.fork_seq("s", "t")
    finally:
        eng.shutdown()


def test_a_preempted_sequence_is_prefilled_again_and_goes_on(params):
    """Under cache pressure on the full pool the latest arrival is evicted:
    both kinds of its blocks go back, it runs its chunks again, and every
    request's tokens are what they are without pressure."""
    prompts = [_prompt(n, seed=n) for n in (90, 100, 110)]

    def served(num_blocks):
        eng = _engine(params, num_blocks=num_blocks, max_num_seqs=3)
        try:
            streams = [eng.submit(p, llm.SamplingParams(max_tokens=40))
                       for p in prompts]
            _run_out(eng, limit=4000)
            return [s.tokens() for s in streams], eng.stats()
        finally:
            eng.shutdown()

    roomy, stats = served(96)
    assert stats["preemptions"] == 0
    tight, stats = served(50)
    assert stats["preemptions"] > 0
    assert tight == roomy
    assert stats["blocks_free"] == 50 and stats["window_blocks"]["held"] == 0


# ----------------------------------------------------------------- the share
@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        held):
    """Every first_held of the small size: the shares' routed parts summed,
    and the shared expert once, are the uncut layer's F, in the program and
    in the reference alike; a share alone is not."""
    whole = dataclasses.replace(CFG, held_experts=0)
    full = afmoe.init_params(jax.random.key(3), whole)
    lp = full["layers"]["l01"]
    h = jax.random.normal(jax.random.key(4), (37, CFG.n_embd))
    h = h / jnp.sqrt((h * h).mean(-1, keepdims=True))   # as a norm leaves it
    want, ids = afmoe._ffn(h, lp, whole)
    shared = afmoe._swiglu(h, lp["shared"], whole)
    # the reference's layer, uncut, under its own choice
    lp32 = ref._widened({k: v for k, v in lp.items() if k != "experts"})
    n, gates, _, _ = ref._route(
        h, jnp.ones(CFG.n_embd), lp32["router"]["kernel"],
        lp32["expert_bias"], None, k=2, eps=0.0, scale=CFG.route_scale)
    total, ref_total = jnp.zeros_like(want), jnp.zeros_like(want)
    for first in range(0, 8, held):
        share = dataclasses.replace(CFG, held_experts=held, first_held=first)
        mine = {**lp, "experts": {k: w[first:first + held]
                                  for k, w in lp["experts"].items()}}
        got, share_ids = afmoe._ffn(h, mine, share)
        assert np.array_equal(share_ids, ids)    # the router is whole
        total = total + got - shared
        ref_total = ref_total + ref.routed_part(h, gates, mine["experts"],
                                                first)
        if held < 8:
            assert float(jnp.abs(got - want).max()) > 1e-2
    assert float(jnp.abs(total + shared - want).max()) < 1e-4
    assert float(jnp.abs(ref_total + ref._swiglu(h, lp32["shared"])
                         - want).max()) < 1e-4


def test_the_count_of_touched_experts_is_of_the_held(params):
    """The number a decode step sends behind its ids counts the distinct
    experts among those held here: what the step reads."""
    eng = _engine(params)
    try:
        assert eng.runner.route_spec == {"layers": 4, "k": 2, "held": (0, 4)}
        stream = eng.submit(_prompt(40), llm.SamplingParams(max_tokens=6))
        _run_out(eng)
        assert len(stream.tokens()) == 6
        steps = eng.stats()["routed_layer_steps"] // 4
        # one live row, 2 choices a layer: at most 2 held experts a layer
        assert 0 <= eng.stats()["experts_touched"] <= 2 * 4 * steps
        ids = np.asarray(eng.runner.choices)[:, :1]          # (4, 1, 2)
        assert eng.runner.choices.shape == (4, 4, 2)
        assert ids.max() < 8
    finally:
        eng.shutdown()


def test_rows_behind_the_held_groups_are_zeroed_where_the_kernel_leaves_them(
        monkeypatch):
    """XLA's TPU ragged-dot writes the rows its groups cover and no other
    (NaNs behind them on the v5e, PR 52): ``grouped_matmul`` zeroes them on
    a TPU where it holds a share, and is the call it was everywhere else."""
    from ray_tpu.ops import moe
    rows = jax.random.normal(jax.random.key(0), (24, 16))
    w = jax.random.normal(jax.random.key(1), (4, 16, 8))
    share = jnp.asarray([3, 0, 2, 1, 9, 0, 5, 4], jnp.int32)    # 6 held rows
    whole = jnp.asarray([3, 9, 2, 10], jnp.int32)

    def lowered(sizes):
        return jax.jit(lambda *a: moe.grouped_matmul(*a)).lower(
            rows, w, sizes).as_text()

    plain = (lowered(share), lowered(whole))
    out = moe.grouped_matmul(rows, w, share)
    assert float(jnp.abs(out[6:]).max()) == 0.0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lowered(whole) == plain[1]            # every group held: as it was
    assert lowered(share).count("stablehlo.select") \
        == plain[0].count("stablehlo.select") + 1
    assert np.array_equal(moe.grouped_matmul(rows, w, share), out)
