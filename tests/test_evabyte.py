"""EvaByte's folded cache at a test's size (``llama:tiny-eva``: windows of
32 positions folded 4 to 1, pages of 8, 4 heads of 16, 2 prediction heads,
float32) against ``perfbench/reference/evabyte_ref.py`` on seeded weights:
the program's ``forward`` (every head), a whole prefill, a prompt in chunks
and decode steps through the paged cache, across one and two closes of a
window in each; the table that shrinks (``serve/llm/kv_cache.py``): two
pages a closed window, the rest back on the free list, ``blocks_needed`` at
every length; preemption and recompute of a folded sequence; the set-ups
that are refused; and faults of the fold that a comparison has to tell from
rounding.

The tiny model computes in float32, so sound runs read 2e-6 to 4e-6; every
limit here is 1e-4."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import evabyte_ref
from ray_tpu.models import llama
from ray_tpu.serve import llm
from ray_tpu.serve.llm import kv_cache
from ray_tpu.serve.llm.kv_cache import PagedKVCache, held_rows, \
    held_rows_most

WINDOW, CHUNK, PAGE, VOCAB = 32, 4, 8, 64
ATOL = 1e-4
SIZES = dict(num_attention_heads=4, rms_norm_eps=1e-5, window_size=WINDOW,
             chunk_size=CHUNK, rope_theta=1e5, vocab_size=VOCAB)


@pytest.fixture(scope="module")
def cfg():
    return llama.PRESETS["tiny-eva"]()


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.key(3), cfg)


def _tokens(seed: int, n: int) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


def _engine(params, **over):
    ecfg = llm.EngineConfig(**{**dict(
        model="llama:tiny-eva", block_size=PAGE, num_blocks=64,
        max_num_seqs=4, max_prefill_tokens=160, max_model_len=160,
        decode_batch_buckets=(4,), prefill_len_buckets=(32, 64, 96, 160),
        share_weights=False), **over})
    return llm.LLMEngine(ecfg, params=params, start=False)


@pytest.fixture(scope="module")
def engine(params):
    eng = _engine(params)
    yield eng
    eng.shutdown()


# ------------------------------------------------------------- the forwards
@pytest.mark.parametrize("length", [20, 32, 33, 64, 70, 97])
def test_forward_gives_every_heads_logits_as_the_reference(cfg, params,
                                                          length):
    """No close, a window just full, one position past a close, two
    windows, two closes and a part-window, three closes."""
    toks = np.asarray([_tokens(length, length)], np.int32)
    got = np.asarray(llama.forward(params, toks, cfg))
    want = evabyte_ref.logits(params, toks, SIZES, heads=True)
    assert got.shape == want.shape == (1, length, 2, VOCAB)
    assert np.abs(got - want).max() < ATOL
    # head 0 is what the serving forwards return
    assert np.array_equal(want[:, :, 0],
                          evabyte_ref.logits(params, toks, SIZES))


@pytest.mark.parametrize("length", [31, 32, 58, 96])
def test_a_whole_prefill_returns_head_0_and_the_rows_held(cfg, params,
                                                         length):
    toks = np.asarray([_tokens(100 + length, length)], np.int32)
    logits, ks, vs = llama.forward_prefill(params, toks, cfg,
                                           last_pos=jnp.int32(length - 1))
    want = evabyte_ref.logits(params, toks, SIZES)[0, -1]
    assert logits.shape == (1, VOCAB)
    assert np.abs(np.asarray(logits[0]) - want).max() < ATOL
    # (L, B, rows, KV, D): the folded rows of the closed windows first
    held = held_rows(length, WINDOW, CHUNK)
    assert ks.shape[:2] == (2, 1) and ks.shape[2] >= held
    assert ks.dtype == vs.dtype == jnp.float32
    if length >= WINDOW:
        # window 0's first folded key is the fold of its first 4 positions
        from ray_tpu.ops.eva_attention import fold_rows
        whole = llama.forward_prefill(params, toks[:, :WINDOW - 1], cfg,
                                      last_pos=jnp.int32(0))
        eva = jax.tree.map(lambda a: a[0], params["blocks"]["eva"])
        kf, vf = fold_rows(whole[1][0, 0, :CHUNK], whole[2][0, 0, :CHUNK],
                           eva["phi"], eva["mu"], CHUNK)
        assert np.allclose(ks[0, 0, 0], kf[0], atol=1e-6)
        assert np.allclose(vs[0, 0, 0], vf[0], atol=1e-6)


def test_training_under_a_fold_is_refused_by_name(cfg, params):
    with pytest.raises(NotImplementedError, match="fold has no backward"):
        llama.forward_hidden(params, jnp.zeros((1, 8), jnp.int32), cfg)


# ------------------------------------- chunks and decode through the cache
def _stepped_as_the_job_steps(eng, prompt: list, steps: int):
    """``perfbench/jobs/serve.py``'s ``TokenStepping.check``: the runner's
    and the cache's public calls, handed the positions SEEN."""
    runner, cache = eng.runner, eng.cache
    n, sid = len(prompt), "check"
    cache.alloc_seq(sid, n)
    tables_seen = [len(cache.table(sid))]
    try:
        logits, ks, vs = runner.prefill(prompt)
        cache.scatter_prefill(sid, np.asarray(ks, np.float32),
                              np.asarray(vs, np.float32), n)
        got, seq = [logits], list(prompt)
        maxb = eng.cfg.max_blocks_per_seq
        for _ in range(steps):
            seq.append(int(np.argmax(got[-1])))
            blk, off, _ = cache.append_slot(sid)
            tables = np.zeros((1, maxb), np.int32)
            table = cache.table(sid)
            tables[0, :len(table)] = table
            tables_seen.append(len(table))
            at = np.asarray([len(seq) - 1], np.int32)
            lg, ks, vs = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                       cache.pool, tables, at)
            cache.write_token(blk, off, np.asarray(ks[:, 0], np.float32),
                              np.asarray(vs[:, 0], np.float32))
            got.append(lg[0])
    finally:
        cache.free_seq(sid)
    return seq, got, tables_seen


@pytest.mark.parametrize("n,steps,closes", [
    (20, 50, 2),        # no close in the prompt, two in decode
    (58, 10, 1),        # one in the prompt's chunks, one in decode
    (96, 40, 1),        # a prompt of three whole windows, one in decode
    (32, 33, 1),        # the prompt's last position closes a window
    (63, 2, 1),         # the first decode step's byte closes one
    (7, 20, 0),         # never a close: a plain cache
])
def test_chunks_and_decode_cross_a_close_as_the_reference(engine, params, n,
                                                          steps, closes):
    cache = engine.cache
    free, folded = cache.free_block_count(), cache.windows_folded
    seq, got, tables = _stepped_as_the_job_steps(engine, _tokens(n, n), steps)
    want = evabyte_ref.logits(params, [seq], SIZES)[0]
    worst = max(float(np.abs(g - want[n - 1 + i]).max())
                for i, g in enumerate(got))
    assert worst < ATOL, worst
    assert cache.windows_folded - folded == closes
    assert cache.free_block_count() == free
    # the table at every step: two pages a closed window, the open one's
    # (step i's slot is that of position n + i - 1: the rows held and one)
    for i, width in enumerate(tables):
        held = cache.held_rows(n + i - 1) + 1 if i else cache.held_rows(n)
        assert width == max(1, -(-held // PAGE)), (i, width)


def test_chunked_prefill_is_the_whole_prefill(engine, params, cfg):
    prompt = _tokens(5, 75)
    chunked, ks, vs = engine.runner.prefill(prompt)
    whole, wk, wv = llama.forward_prefill(
        params, np.asarray([prompt], np.int32), cfg, last_pos=jnp.int32(74))
    assert np.abs(chunked - np.asarray(whole[0])).max() < ATOL
    held = held_rows(75, WINDOW, CHUNK)
    assert np.allclose(ks[:, :held], wk[:, 0, :held], atol=1e-5)
    assert np.allclose(vs[:, :held], wv[:, 0, :held], atol=1e-5)


def test_the_fold_out_of_the_pool_is_the_fold_of_its_rows(engine, params):
    """``runner.fold_windows``: a window's 4 pages read, its 8 folded rows
    written over the first of them, nothing else of the pool touched."""
    from ray_tpu.ops.eva_attention import fold_rows
    cache, rng = engine.cache, np.random.default_rng(0)
    cache.alloc_seq("w", WINDOW)
    pages = cache.table("w")
    # alloc_seq took the prompt's window as folded: one page
    assert len(pages) == WINDOW // CHUNK // PAGE == 1
    cache.free_seq("w")
    cache.alloc_seq("w", WINDOW - 1)
    pages = cache.table("w")
    assert len(pages) == WINDOW // PAGE
    k = rng.normal(size=(2, WINDOW, 4, 16)).astype(np.float32)
    v = rng.normal(size=(2, WINDOW, 4, 16)).astype(np.float32)
    cache.scatter_prefill("w", k, v, WINDOW - 1)
    cache.write_token(pages[-1], PAGE - 1, k[:, -1], v[:, -1])
    before = cache.blocks()
    engine.runner.fold_windows(np.asarray(pages, np.int32))
    after = cache.blocks()
    eva = params["blocks"]["eva"]
    for layer in range(2):
        kf, vf = fold_rows(k[layer], v[layer], eva["phi"][layer],
                           eva["mu"][layer], CHUNK)
        assert np.allclose(after[pages[0], layer, 0], kf, atol=1e-6)
        assert np.allclose(after[pages[0], layer, 1], vf, atol=1e-6)
    others = [b for b in range(cache.num_blocks) if b != pages[0]]
    assert np.array_equal(before[others], after[others])
    cache.free_seq("w")


# ------------------------------------------------------ the table that shrinks
def _cache(**over) -> PagedKVCache:
    cache = PagedKVCache(**{**dict(
        num_blocks=64, n_layer=1, block_size=PAGE, n_kv=1, head_dim=128,
        fold_window=WINDOW, fold_chunk=CHUNK), **over})
    cache.closed = []
    cache.folder = lambda pages: cache.closed.append(list(pages))
    return cache


@pytest.mark.parametrize("window,chunk,page,most", [
    (WINDOW, CHUNK, PAGE, 160), (2048, 16, 64, 26624)])
def test_blocks_needed_at_every_length(window, chunk, page, most):
    """``2 floor(n / 2048) + ceil((n mod 2048) / 64)`` at the published
    sizes, at most ``2 (ceil(max / 2048) - 1) + 32``."""
    cache = _cache(fold_window=window, fold_chunk=chunk, block_size=page,
                   num_blocks=4)
    per = window // chunk // page
    n = np.arange(1, most + 1)
    want = np.maximum(1, per * (n // window) + -(-(n % window) // page))
    assert [cache.blocks_needed(int(x)) for x in n[::7]] == list(want[::7])
    assert cache.blocks_needed(most) == want[-1]
    # the most on the way there: a filled window still exact, not yet folded
    exact = (n // window - 1) * (window // chunk) + window
    assert held_rows_most(most, window, chunk) == max(
        held_rows(n, window, chunk).max(), exact[n % window == 0].max())
    assert held_rows_most(window + 1, window, chunk) == window
    if window == 2048:
        assert -(-held_rows_most(26624, 2048, 16) // 64) == 56
        assert cache.blocks_needed(26624) == 26
        assert held_rows_most(26624, 2048, 16) == 3584
        assert held_rows(26624 - 1, 2048, 16) == 12 * 128 + 2047


def test_the_table_after_each_close():
    cache = _cache()
    n = 5
    first = cache.alloc_seq("s", n)
    assert len(first) == 1
    held = []
    for seen in range(n, 3 * WINDOW + 3):
        free = cache.free_block_count()
        blk, off, grew = cache.append_slot("s")
        table = cache.table("s")
        closed = seen // WINDOW
        # the slot is that of the next row HELD
        row = held_rows(seen, WINDOW, CHUNK)
        assert (blk, off) == (table[row // PAGE], row % PAGE)
        assert len(table) == closed + (seen % WINDOW) // PAGE + 1
        if seen and seen % WINDOW == 0:
            # a close: the window's 4 pages read, its first kept, 3 given
            # back at once (and one taken for the open window's first row)
            assert len(cache.closed) == closed
            assert cache.closed[-1][0] == table[closed - 1]
            assert cache.free_block_count() == free + 3 - 1
        held.append(len(table))
    assert len(cache.closed) == 3 and cache.windows_folded == 3
    assert max(held) == 2 + WINDOW // PAGE          # just before the third
    assert cache.free_seq("s") == 3 + 1             # 3 folded, 1 open
    assert cache.free_block_count() == 64


def test_a_slot_given_back_after_a_close_keeps_the_fold():
    cache = _cache()
    cache.alloc_seq("s", WINDOW - 1)
    cache.append_slot("s")                          # the window's last row
    assert cache.fill("s") == WINDOW
    _, _, grew = cache.append_slot("s")             # closes it, and grows
    assert grew and len(cache.closed) == 1 and len(cache.table("s")) == 2
    cache.rollback_slot("s", grew)
    assert cache.fill("s") == WINDOW and len(cache.table("s")) == 1
    blk, off, grew = cache.append_slot("s")         # no second fold
    assert grew and off == 0 and len(cache.closed) == 1
    assert cache.table("s")[1] == blk


def test_a_prompts_closed_windows_are_allocated_folded():
    cache = _cache()
    table = cache.alloc_seq("s", 2 * WINDOW + 5)
    assert len(table) == 2 + 1
    cache.append_slot("s")
    assert not cache.closed                         # nothing left to fold
    assert cache._fold_holds() == {
        "fold_blocks_held": 3, "fold_blocks_unfolded": -(-70 // PAGE),
        "windows_folded": 0}


def test_a_window_without_a_folder_is_refused_by_name():
    cache = _cache()
    cache.folder = None
    cache.alloc_seq("s", WINDOW)
    cache.append_slot("s")          # folded by the prompt already
    cache.free_seq("s")
    cache.alloc_seq("s", WINDOW - 1)
    cache.append_slot("s")
    with pytest.raises(RuntimeError, match="handed no folder"):
        cache.append_slot("s")


@pytest.mark.parametrize("over,why", [
    (dict(block_size=16), "no whole number of pages"),      # 8 rows a window
    (dict(fold_chunk=5), "no whole number of pages"),       # 32 / 5
    (dict(fold_chunk=0), "no whole number of pages"),
    (dict(select_stride=4), "K/V under one table alone"),
    (dict(window_layers=1, window=16, max_seqs=2), "one table alone"),
])
def test_a_set_up_the_fold_is_not_written_for_is_refused(over, why):
    with pytest.raises(ValueError, match=why):
        _cache(**over)


@pytest.mark.parametrize("over", [
    dict(eva_chunk=0), dict(eva_chunk=5), dict(prefill_chunk=16),
    dict(n_experts=4, experts_per_token=2), dict(block_length=4)])
def test_a_preset_the_fold_is_not_written_for_is_refused(cfg, over):
    import dataclasses
    with pytest.raises(ValueError, match="a folded cache needs"):
        dataclasses.replace(cfg, **over)


def test_a_folded_sequence_is_neither_forked_nor_exported(engine):
    cache = engine.cache
    cache.alloc_seq("a", 10)
    with pytest.raises(NotImplementedError, match="shrinks cannot be forked"):
        cache.fork_seq("a", "b")
    cache.free_seq("a")
    with pytest.raises(NotImplementedError, match="closed windows folded"):
        engine.prefill_remote([1, 2, 3], llm.SamplingParams(max_tokens=2))
    assert cache.unexported() and cache.unshared()


def test_a_model_that_does_not_fold_has_nothing_of_it():
    from ray_tpu.models import gpt2
    for mod, preset in ((llama, "tiny"), (llama, "tiny-keye"),
                        (gpt2, "tiny")):
        kept = kv_cache.kept_by(mod, mod.PRESETS[preset]())
        assert (kept.fold_window, kept.fold_chunk) == (0, 0)
    cache = PagedKVCache(8, 1, 8, 1, 128)
    assert cache.held_rows(77) == 77 and cache.blocks_needed(17) == 3
    assert cache.held_counts() == {} and not cache.unexported()
    kv = next(p for p in cache.planes if p.name == "kv")
    assert kv.reads is None and kv.holds is None
    assert held_rows(jnp.int32(77), 0, 0) == 77


def test_the_reads_are_the_mathematics():
    reads = kv_cache.fold_reads([0, 31, 32, 100], False, WINDOW, CHUNK, 2)
    assert reads == {"rows_read": 2 * (0 + 31 + 8 + 28),
                     "positions_seen": 2 * (0 + 31 + 32 + 100)}
    # a chunk's queries see their own position too
    chunk = kv_cache.fold_reads(np.arange(64, 70), True, WINDOW, CHUNK, 2)
    assert chunk == {"rows_read": 2 * sum(16 + t + 1 for t in range(6)),
                     "positions_seen": 2 * sum(range(65, 71))}


# ------------------------------------------------------------- the engine
def _served(eng, requests):
    eng.start()
    streams = [eng.submit(p, llm.SamplingParams(max_tokens=m))
               for p, m in requests]
    return [s.tokens() for s in streams]


def _is_the_references_greedy(params, prompt: list, out: list) -> bool:
    """``out`` is the reference's greedy continuation of ``prompt``: one
    forward over both, each byte the argmax at the position before it."""
    seq = list(prompt) + list(out)
    logits = evabyte_ref.logits(params, [seq], SIZES)[0]
    return [int(t) for t in np.argmax(logits[len(prompt) - 1:-1], -1)] \
        == list(out)


def test_the_engine_serves_across_closes_and_counts_them(params):
    requests = [(_tokens(40, 30), 40), (_tokens(41, 64), 12),
                (_tokens(42, 7), 30), (_tokens(43, 90), 10)]
    eng = _engine(params)
    try:
        outs = _served(eng, requests)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert [len(o) for o in outs] == [m for _, m in requests]
    for (prompt, n), out in zip(requests, outs):
        assert _is_the_references_greedy(params, prompt, out)
    # 30 + 40 crosses 32 and 64; 64 + 12 none; 7 + 30 one; 90 + 10 one (96)
    assert stats["windows_folded"] == 4 and stats["fold_window"] == WINDOW
    assert 0 < stats["rows_read"] < stats["positions_seen"]
    assert stats["preemptions"] == 0
    assert stats["blocks_free"] == eng.cfg.num_blocks
    assert eng.span_s["llm.window.fold"][0] == 4 + 1     # and the warm-up's


def test_a_folded_sequence_is_preempted_and_recomputed(params):
    """A pool too small for both at their longest: the later arrival is
    evicted, re-prefilled with its tokens so far (its closed windows
    folded by its chunks this time) and ends with the same bytes."""
    requests = [(_tokens(50, 28), 60), (_tokens(51, 30), 60)]
    eng = _engine(params, num_blocks=9, max_num_seqs=2,
                  decode_batch_buckets=(2,))
    try:
        outs = _served(eng, requests)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert stats["preemptions"] >= 1
    assert [len(o) for o in outs] == [60, 60]
    for (prompt, _), out in zip(requests, outs):
        assert _is_the_references_greedy(params, prompt, out)
    assert stats["blocks_free"] == 9


def test_the_scheduler_admits_by_the_rows_held(params):
    """A prompt of 150 positions holds 4 x 1 + 3 pages, not 19: a pool of
    10 pages admits it."""
    eng = _engine(params, num_blocks=10, max_num_seqs=1,
                  decode_batch_buckets=(1,))
    try:
        assert eng.cache.blocks_needed(150) == 4 + 3
        out, = _served(eng, [(_tokens(60, 150), 5)])
    finally:
        eng.shutdown()
    assert len(out) == 5


def test_the_counters_are_in_the_catalog_and_the_step_series():
    from ray_tpu.serve.llm.engine import STEP_SERIES
    from ray_tpu.util import metrics_catalog as mcat
    for name in ("fold_blocks_held", "fold_blocks_unfolded",
                 "windows_folded"):
        series, how = STEP_SERIES[name]
        assert how == "inc" and mcat.CATALOG[series]["kind"] == "counter"
    assert STEP_SERIES["windows_folded"][0] == "rtpu_llm_kv_windows_folded"


# ------------------------------------------- faults a comparison has to see
@pytest.mark.parametrize("fault", evabyte_ref.FAULTS)
def test_a_broken_fold_is_told_from_rounding(cfg, params, fault):
    """On contrived keys (W_k scaled so that a chunk's softmax and the
    queries' are sharp): a wrong ``a``, a missing ``mu``, a window's rows
    left out, visible a window early or cut short each move logits by
    thousands of times what rounding does."""
    sharp = jax.tree.map(lambda a: a, params)
    sharp["blocks"] = {**params["blocks"], "wk": {
        "kernel": params["blocks"]["wk"]["kernel"] * 3.0}}
    toks = np.asarray([_tokens(9, 97)], np.int32)
    got = np.asarray(llama.forward(sharp, toks, cfg))
    sound = np.abs(got - evabyte_ref.logits(sharp, toks, SIZES,
                                            heads=True)).max()
    broken = np.abs(got - evabyte_ref.logits(sharp, toks, SIZES, heads=True,
                                             fault=fault)).max()
    assert sound < ATOL and broken > 1000 * sound and broken > 0.1


@pytest.mark.parametrize("fault", ["uniform_a", "no_mu"])
def test_the_programs_fold_broken_reads_as_the_references(params, fault):
    """``benchmarks/evabyte_check.py``'s two faults of the PROGRAM against
    the sound reference, beside the sound program against the broken
    reference: the same difference, seen from either side."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "evabyte_check", Path(__file__).parent.parent / "benchmarks"
        / "evabyte_check.py")
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)
    toks = np.asarray([_tokens(11, 80)], np.int32)
    cfg = llama.PRESETS["tiny-eva"]()
    with check.broken(fault):
        got = np.asarray(llama.forward(params, toks, cfg))
    want = evabyte_ref.logits(params, toks, SIZES, heads=True)
    other = np.abs(np.asarray(llama.forward(params, toks, cfg))
                   - evabyte_ref.logits(params, toks, SIZES, heads=True,
                                        fault=fault)).max()
    mine = np.abs(got - want).max()
    assert mine > 0.05 and 0.5 < mine / other < 2.0


def test_the_published_preset_is_the_configuration_files(cfg):
    big = llama.PRESETS["evabyte-6.5b-l8"]()
    file = json.loads((Path(__file__).parent.parent / "perfbench" / "configs"
                       / "evabyte-6.5b.json").read_text())
    assert (big.n_embd, big.n_head, big.n_kv_head, big.ffn_dim, big.n_layer,
            big.vocab_size, big.eva_window, big.eva_chunk, big.pred_heads) \
        == (4096, 32, 32, 11008, 8, 320, 2048, 16, 8)
    assert big.head_dim == 128 and file["num_hidden_layers"] == big.n_layer
    assert llama.folded_cache(big) == {"window": 2048, "chunk": 16}
    assert llama.folded_cache(llama.PRESETS["tiny"]()) is None
    spec = llama.prefill_staging(big, 26624)
    assert spec["k"].shape == (8, 3584, 4096) and set(spec) == {"k", "v"}
