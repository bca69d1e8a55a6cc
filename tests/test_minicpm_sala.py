"""MiniCPM-SALA on the CPU at the ``tiny`` preset, float32, seeded weights:
the program (chunked prefill, the selector's cache, the paged sparse decode,
Lightning through ``ops/ssm.py``) against the plain reference
(``perfbench/reference/minicpm_sala_ref.py``), which shares no code with it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.families import minicpm_sala as family
from perfbench.reference import minicpm_sala_ref as ref
from ray_tpu.models import minicpm_sala as sala
from ray_tpu.ops import sparse_attention as sparse
from ray_tpu.ops import ssm
from ray_tpu.serve import llm
from ray_tpu.serve.llm.kv_cache import PagedKVCache

CFG = sala.tiny()
SPEC = CFG.sparse                  # pages of 8, 4 chosen past 48 positions


@pytest.fixture(scope="module")
def params():
    return sala.init_params(jax.random.key(1), CFG)


def _engine(params=None, **over):
    cfg = llm.EngineConfig(**{**dict(
        model="minicpm_sala:tiny", block_size=8, num_blocks=64,
        max_num_seqs=4, max_prefill_tokens=128, max_model_len=128,
        decode_batch_buckets=(4,), prefill_len_buckets=(32, 64, 128),
        share_weights=False), **over})
    return llm.LLMEngine(cfg, params=params, start=False)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 128, n)]


def _reference(params, tokens):
    return ref.logits(params, np.asarray([tokens]),
                      family.sizes_of_model(CFG))[0]


def _run_out(eng, limit=400):
    for _ in range(limit):
        if not eng.step() and not eng.sched.has_work():
            return
    raise AssertionError("the engine did not finish")


# ------------------------------------------------------ program vs reference
@pytest.mark.parametrize("n", [20, 48, 49, 100],
                         ids=["dense", "at_dense_len", "first_sparse",
                              "sparse"])
def test_one_run_prefill_is_the_reference(params, n):
    """Every position's logits, a context under ``dense_len`` (48) and
    over it: past it each query reads block 0, the blocks of its last 8
    positions and the best others, 4 in all."""
    tokens = _prompt(n)
    got = np.asarray(sala.forward(params, jnp.asarray([tokens]), CFG))[0]
    np.testing.assert_allclose(got, _reference(params, tokens), atol=5e-5)


@pytest.mark.parametrize("n", [30, 70, 100], ids=["one", "three", "four"])
def test_prefill_in_chunks_is_prefill_in_one_run(params, n):
    """The runner's prefill (chunks of 32 over the staging K/V and the
    store's staging row) gives the logits, the K/V and the state that the
    whole prompt gives as one run."""
    eng = _engine(params)
    try:
        tokens = _prompt(n, seed=n)
        logits, ks, vs = eng.runner.prefill(tokens)
        want, wk, wv, state = sala.forward_prefill(
            params, jnp.asarray([tokens]), CFG, last_pos=jnp.int32(n - 1))
        np.testing.assert_allclose(logits, np.asarray(want)[0], atol=5e-5)
        np.testing.assert_allclose(np.asarray(ks)[:, :n],
                                   np.asarray(wk)[:, 0], atol=1e-5)
        np.testing.assert_allclose(np.asarray(vs)[:, :n],
                                   np.asarray(wv)[:, 0], atol=1e-5)
        staged = eng.cache.pool.read(lambda held: held["state"]["s"][:, -1])
        np.testing.assert_allclose(np.asarray(staged),
                                   np.asarray(state["s"])[:, 0], atol=1e-5)
        assert eng.runner.compiles == 1          # one chunk program
    finally:
        eng.shutdown()


@pytest.mark.parametrize("n", [30, 70], ids=["one_chunk", "three_chunks"])
def test_paged_decode_after_prefill_is_the_reference(params, n):
    """Greedy decoding through the paged pool, the selector's cache and the
    state rows, across ``dense_len``: each step's logits are the
    reference's for the sequence so far."""
    eng = _engine(params)
    try:
        runner, cache = eng.runner, eng.cache
        tokens = _prompt(n, seed=7)
        cache.alloc_seq("s", n)
        logits, ks, vs = runner.prefill(tokens)
        cache.scatter_prefill("s", ks, vs, n)
        got, seq = [logits], list(tokens)
        for _ in range(30):
            seq.append(int(np.argmax(got[-1])))
            cache.append_slot("s")
            tables = np.zeros((1, eng.cfg.max_blocks_per_seq), np.int32)
            table = cache.table("s")
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, _, _ = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                     cache.pool, tables, at)
            got.append(lg[0])
        want = _reference(params, seq)
        for i, g in enumerate(got):
            np.testing.assert_allclose(g, want[n - 1 + i], atol=1e-4)
        assert len(seq) > SPEC.dense_len        # the last steps selected
    finally:
        eng.shutdown()


# ------------------------------------------------------------- the selection
AT = 99                             # the query the contrived keys are for


def _contrived(t=104):
    """One KV head, 2 query heads of 8: a query at position 99 whose keys of
    note lie in block 0, in a far block (5: positions 40-47) and in its own
    block (12); every other key points away from it."""
    rng = np.random.default_rng(3)
    d = 8
    q = np.zeros((t, 1, 2, d), np.float32)
    q[:, 0, :, 0] = 4.0
    k = rng.normal(size=(t, 1, d)).astype(np.float32) * 0.05
    k[:, 0, 0] = -1.0
    for block, strength in ((0, 1.5), (5, 2.0), (AT // 8, 1.5)):
        k[block * 8:block * 8 + 8, 0, 0] = strength
    v = rng.normal(size=(t, 1, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _attend(q, k, v, mask):
    t = q.shape[0]
    return np.asarray(sparse.prefill_attention(
        q, k.reshape(t, -1), v.reshape(t, -1), mask, jnp.arange(t), t,
        SPEC.block))


def test_each_part_of_the_selection_changes_the_result_when_dropped():
    """The query at 99 reads block 0 (forced), blocks 11 and 12 (its last 8
    positions, 92-99) and block 5 (the best-scoring of the others), and the
    program's result is the reference's; without the top-k pick, without
    block 0 or without the local blocks the result is another."""
    q, k, v = _contrived()
    t = q.shape[0]
    halves = sparse.halves_of(k.reshape(t, -1), t, SPEC.stride)
    mask = sparse.prefill_mask(q, halves.reshape(-1, 1, 8), jnp.arange(t),
                               SPEC)
    assert list(np.flatnonzero(np.asarray(mask)[0, AT])) == [0, 5, 11, 12]
    full = _attend(q, k, v, mask)
    kc = jnp.stack([k[j:j + SPEC.kernel].mean(0)
                    for j in range(0, t - SPEC.kernel + 1, SPEC.stride)])
    want = np.asarray(ref._sparse_block(
        q, k, v, kc, jnp.arange(t),
        spec=(*(int(x) for x in SPEC), t)))
    np.testing.assert_allclose(full, want, atol=1e-5)
    for name, dropped in (("top-k", [5]), ("first", [0]),
                          ("local", [11, 12])):
        less = np.asarray(mask).copy()
        less[0, AT, dropped] = False
        other = _attend(q, k, v, jnp.asarray(less))
        assert np.abs(other[AT] - full[AT]).max() > 0.05, name
        np.testing.assert_allclose(other[:SPEC.dense_len],
                                   full[:SPEC.dense_len], atol=1e-6)


def test_ties_go_to_the_lower_block_and_forced_blocks_count():
    """Equal scores everywhere: the free picks are the lowest blocks, and
    the forced ones are among the ``topk``, not beside them."""
    logits = jnp.zeros((1, 2, 15 * 4 - 1))
    ids, count = sparse.choose_blocks(logits, jnp.asarray([100]), SPEC)
    # block 0 forced, 11-12 hold positions 93..100, one pick left: block 1
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 1, 11, 12]
    assert int(count[0]) == SPEC.topk


# --------------------------------------------------------- the selector cache
def test_the_selectors_cache_holds_the_half_kernels_of_the_keys_whole():
    """``scatter_prefill`` and ``write_token`` fill it from the K they
    write: after a prompt of 37 and 9 tokens more, every half-kernel of the
    sequence's pages is the sum of its (up to) 2 keys, a page that a dead
    sequence left behind notwithstanding."""
    rng = np.random.default_rng(5)
    cache = PagedKVCache(16, 2, 8, 2, 8, select_stride=2)
    cache.alloc_seq("old", 60)
    junk = rng.normal(size=(2, 64, 2, 8)).astype(np.float32)
    cache.scatter_prefill("old", junk, junk, 60)
    cache.free_seq("old")
    k = rng.normal(size=(2, 46, 2, 8)).astype(np.float32)
    v = rng.normal(size=(2, 46, 2, 8)).astype(np.float32)
    padded = np.zeros((2, 64, 2, 8), np.float32)
    padded[:, :37] = k[:, :37]
    cache.alloc_seq("s", 37)
    cache.scatter_prefill("s", padded, padded, 37)
    for t in range(37, 46):
        blk, off, _ = cache.append_slot("s")
        cache.write_token(blk, off, k[:, t], v[:, t])
        cache.write_token(blk, off, k[:, t], v[:, t])   # again: no change
    table = cache.table("s")
    held = cache.half_kernels()[:, table].reshape(2, -1, 128)[:, :23, :16]
    want = np.asarray(sparse.halves_of(
        jnp.asarray(k.reshape(2, 46, 16)), 46, 2))
    np.testing.assert_allclose(held, want, atol=1e-6)
    assert cache.free_seq("s") == len(table)
    assert cache.free_block_count() == 16


# ------------------------------------------------------------------ lightning
def test_lightning_in_chunks_is_the_recurrence_token_by_token():
    """``ssd_scan`` over three runs of a sequence, each from the state the
    run before left (``state0=``), is ``ssm_step`` over its tokens; padding
    behind the last real position (dt = 0) leaves the state as it is."""
    rng = np.random.default_rng(2)
    t, h, d = 40, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
               for _ in range(3))
    a = -sala.slopes(CFG)
    state = jnp.zeros((1, h, d, d))
    want = []
    for i in range(t):
        y, state = ssm.ssm_step(state, v[:, i], jnp.ones((1, h)), a,
                                k[:, i], q[:, i])
        want.append(y)
    got, carried = [], None
    for lo, hi, real in ((0, 16, 16), (16, 32, 16), (32, 48, 8)):
        pad = hi - t if hi > t else 0
        part = [jnp.pad(x[:, lo:hi], ((0, 0), (0, pad), (0, 0), (0, 0)))
                for x in (v, k, q)]
        dt = (jnp.arange(hi - lo) < real).astype(jnp.float32)
        y, carried = ssm.ssd_scan(
            part[0], jnp.broadcast_to(dt[None, :, None], (1, hi - lo, h)), a,
            part[1], part[2], 8, state0=carried)
        got.append(y[:, :real])
    np.testing.assert_allclose(np.concatenate(got, 1),
                               np.stack(want, 1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(carried, state, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ the loop
def _alone(params, prompt, max_tokens):
    eng = _engine(params)
    try:
        stream = eng.submit(prompt, llm.SamplingParams(max_tokens=max_tokens))
        _run_out(eng)
        return stream.poll(max_items=256, timeout=0)[0]
    finally:
        eng.shutdown()


def test_a_decode_step_runs_between_two_chunks_and_changes_neither(params):
    """A sequence decodes while another's prompt of four chunks is
    prefilled: every iteration with a chunk also decodes, both requests
    get the tokens they get alone, and ``prefill_steps`` counts chunks."""
    a, b = _prompt(20, seed=11), _prompt(100, seed=12)
    want_a, want_b = _alone(params, a, 40), _alone(params, b, 12)
    eng = _engine(params)
    try:
        sa = eng.submit(a, llm.SamplingParams(max_tokens=40))
        eng.step()                                   # a's one chunk
        eng.step()
        sb = eng.submit(b, llm.SamplingParams(max_tokens=12))
        kinds = []
        for _ in range(4):
            before = eng.stats()
            eng.step()
            after = eng.stats()
            kinds.append((after["prefill_chunks"] - before["prefill_chunks"],
                          after["decode_steps"] - before["decode_steps"]))
        assert kinds == [(1, 1)] * 4
        assert eng.stats()["waiting"] == 0 and eng.stats()["running"] == 2
        _run_out(eng)
        assert sa.poll(max_items=256, timeout=0)[0] == want_a
        assert sb.poll(max_items=256, timeout=0)[0] == want_b
        stats = eng.stats()
        assert stats["prefill_steps"] == stats["prefill_chunks"] == 5
        assert 0 < stats["sparse_pages_read"] < stats["sparse_pages_held"]
        assert "llm.prefill.chunk" in stats["span_s"]
    finally:
        eng.shutdown()


def test_preemption_and_cancel_return_pages_kernels_and_row(params):
    """A pool too small for both sequences to finish preempts the later
    one, which is prefilled again (in chunks) and ends with the tokens it
    gets alone; a prompt cancelled part-way through its chunks gives its
    pages and its row back."""
    a, b = _prompt(40, seed=21), _prompt(40, seed=22)
    want_a, want_b = _alone(params, a, 30), _alone(params, b, 30)
    eng = _engine(params, num_blocks=16)
    try:
        sa = eng.submit(a, llm.SamplingParams(max_tokens=30))
        sb = eng.submit(b, llm.SamplingParams(max_tokens=30))
        _run_out(eng)
        assert eng.stats()["preemptions"] >= 1
        assert sa.poll(max_items=256, timeout=0)[0] == want_a
        assert sb.poll(max_items=256, timeout=0)[0] == want_b
        assert eng.cache.free_block_count() == 16
        assert eng.cache.state_rows_used() == 0
        sc = eng.submit(_prompt(100, seed=23),
                        llm.SamplingParams(max_tokens=4))
        eng.step()
        eng.step()
        assert eng.sched.prefilling is not None
        assert eng.cache.free_block_count() < 16
        sc.cancel()
        eng.step()
        assert eng.sched.prefilling is None and not eng.sched.has_work()
        assert eng.cache.free_block_count() == 16
        assert eng.cache.state_rows_used() == 0
    finally:
        eng.shutdown()


# ------------------------------------------------------- the listed walk
def test_the_kernels_listed_walk_is_the_gathers():
    """``paged_attention_decode`` under a list of pages a (row, KV head):
    the Pallas kernel (interpret mode) against the gather path, lists in
    any order, a count of 0, a row padded up to the bucket; and the list
    of every page is the call without one."""
    from ray_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(0)
    b, h, kv, d, bs, n, maxb, width = 3, 8, 2, 128, 16, 40, 12, 6
    pool = jnp.asarray(rng.normal(size=(2, 2, n, bs, kv * d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(n)[:b * maxb].reshape(b, maxb),
                         jnp.int32)
    lens = jnp.asarray([150, 37, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, kv, d)), jnp.float32)
    pages = jnp.asarray(rng.integers(0, 10, size=(b, kv, width)), jnp.int32)
    pages = pages.at[0, 0].set(jnp.asarray([9, 0, 3, 5, 2, 7]))
    counts = jnp.asarray([[6, 4], [3, 2], [0, 0]], jnp.int32)
    want = pa._paged_decode_gather(q, pool, 1, tables, lens, k_new, v_new,
                                   pages, counts)
    got = pa._paged_decode_kernel(q, pool, 1, tables, lens, k_new, v_new,
                                  pages, counts, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    every = jnp.broadcast_to(jnp.arange(maxb)[None, None], (b, kv, maxb))
    held = jnp.broadcast_to((-(-lens // bs))[:, None], (b, kv))
    plain = pa._paged_decode_gather(q, pool, 1, tables, lens, k_new, v_new)
    for walk in (pa._paged_decode_gather,
                 lambda *a: pa._paged_decode_kernel(*a, interpret=True)):
        np.testing.assert_allclose(
            walk(q, pool, 1, tables, lens, k_new, v_new, every, held),
            plain, atol=2e-6)


@pytest.mark.parametrize("start,chosen", [(0, 1.0), (512, 0.3), (768, 0.05)],
                         ids=["first_chunk", "a_third", "few"])
def test_the_prefill_kernel_is_the_masked_tiles(start, chosen):
    """``prefill_attention``'s flash kernel (interpret mode) against the
    plain tiles, a run of queries part-way through a staging: each query
    its own blocks, none past its own and its own among them, as the
    selection hands them over; tiles nobody chose and the tiles past the
    run's end are skipped."""
    rng = np.random.default_rng(start)
    t, kv, rep, d, s_len, block = 256, 2, 4, 128, 1536, 64
    q = jnp.asarray(rng.normal(size=(t, kv, rep, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s_len, kv * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s_len, kv * d)), jnp.float32)
    pos = start + jnp.arange(t)
    b = jnp.arange(s_len // block)
    own = (pos // block)[None, :, None]
    mask = (jnp.asarray(rng.random((kv, t, s_len // block)) < chosen)
            & (b[None, None, :] < own)) | (b[None, None, :] == own)
    want = sparse._masked_tiles(q, k, v, mask, pos, start + t, block)
    got = sparse._flash_masked(q, k, v, mask, pos, start + t, block,
                               interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_engine_refuses_a_page_that_is_not_the_selections():
    with pytest.raises(ValueError, match="selects pages of 8"):
        _engine(block_size=16)
    with pytest.raises(ValueError, match="whole chunks"):
        _engine(prefill_len_buckets=(48, 128))


def test_a_chunking_module_without_state_rows_is_handed_no_holder():
    # a chunk hands the next its recurrent state in the store's staging row
    # where the family has a store (this one: the holder goes through the
    # chunk program, donated); a family that prefills in chunks and carries
    # K/V alone (afmoe, PR 52) runs the same chunk body with no holder, as
    # a one-program prefill without a store does, and what its chunks carry
    # is the staging
    from ray_tpu.serve.llm.model_runner import _NoHolder
    eng = _engine()
    try:
        assert eng.runner.chunk == CFG.prefill_chunk
        assert eng.runner._prefill_holder() is eng.cache.pool
    finally:
        eng.shutdown()
    eng = _engine(model="afmoe:tiny")
    try:
        assert eng.runner.chunk and eng.runner.state_spec is None
        assert eng.runner._prefill_holder is _NoHolder
        eng.start()
        assert len(eng.generate(_prompt(70),
                                llm.SamplingParams(max_tokens=3))) == 3
    finally:
        eng.shutdown()
