"""serve.llm — continuous-batching engine, paged KV cache, data-plane
prefill/decode handoff, serve integration (ISSUE 6 / DESIGN.md §4g).

The correctness oracle throughout is the models' FULL forward pass:
greedy decode through the paged engine must produce byte-identical
token streams to recompute-everything greedy decode, for both model
families, with and without batching, preemption, and handoff.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from conftest import time_scale
from ray_tpu.serve.llm import (EngineConfig, LLMEngine, SamplingParams,
                               llm_deployment, naive_llm_deployment)
from ray_tpu.serve.llm import kv_cache as kvmod
from ray_tpu.serve.llm.config import resolve_model
from ray_tpu.serve.llm.kv_cache import NoFreeBlocks, PagedKVCache
from ray_tpu.serve.llm.scheduler import (IterationScheduler, SamplingParams
                                         as _SP, Sequence)


def tiny_cfg(model="gpt2:tiny", **kw):
    base = dict(model=model, num_blocks=64, block_size=8, max_num_seqs=4,
                max_model_len=64, max_prefill_tokens=32,
                prefill_len_buckets=(16, 32, 64),
                decode_batch_buckets=(1, 2, 4),
                share_weights=False)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture
def engine():
    eng = LLMEngine(tiny_cfg())
    yield eng
    eng.shutdown()


def oracle_decode(eng, prompt, n):
    """Greedy reference: full-forward recompute per token."""
    mod, mcfg = resolve_model(eng.cfg)
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = mod.forward(eng.runner.params,
                             np.asarray([toks], np.int32), mcfg)
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


# ------------------------------------------------------------ op level
def test_paged_attention_matches_dense():
    """gather-through-block-table attention == dense softmax ref."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_attention_decode
    rng = np.random.default_rng(0)
    B, H, KV, D, bs, N, maxb = 2, 4, 2, 8, 4, 16, 3
    q = rng.standard_normal((B, H, D), np.float32)
    pool_k = rng.standard_normal((N, bs, KV, D), np.float32)
    pool_v = rng.standard_normal((N, bs, KV, D), np.float32)
    tables = np.array([[3, 7, 1], [5, 2, 0]], np.int32)
    lens = np.array([10, 5], np.int32)
    k_new = rng.standard_normal((B, KV, D), np.float32)
    v_new = rng.standard_normal((B, KV, D), np.float32)
    # the engine's pool as the device holds it, one layer of it
    pool = np.zeros(kvmod.device_shape(N, 1, bs, KV, D), np.float32)
    pool[0, 0, :, :, :KV * D] = pool_k.reshape(N, bs, KV * D)
    pool[0, 1, :, :, :KV * D] = pool_v.reshape(N, bs, KV * D)
    got = np.asarray(paged_attention_decode(
        jnp.asarray(q), jnp.asarray(pool), 0,
        jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(k_new),
        jnp.asarray(v_new)))
    rep = H // KV
    for b in range(B):
        k_ctx = pool_k[tables[b]].reshape(-1, KV, D)[:lens[b]]
        v_ctx = pool_v[tables[b]].reshape(-1, KV, D)[:lens[b]]
        k_all = np.concatenate([k_ctx, k_new[b][None]], 0).repeat(rep, 1)
        v_all = np.concatenate([v_ctx, v_new[b][None]], 0).repeat(rep, 1)
        for h in range(H):
            logit = (q[b, h] @ k_all[:, h].T) / np.sqrt(D)
            p = np.exp(logit - logit.max())
            p /= p.sum()
            ref = p @ v_all[:, h]
            np.testing.assert_allclose(got[b, h], ref, rtol=2e-4,
                                       atol=2e-5)


# --------------------------------------------------------- cache units
def test_kv_cache_alloc_refcount_and_pressure():
    c = PagedKVCache(num_blocks=4, n_layer=1, block_size=2, n_kv=1,
                     head_dim=4)
    try:
        c.alloc_seq("a", 3)                       # 2 blocks
        assert c.free_block_count() == 2
        c.fork_seq("a", "b")                      # shared, no new blocks
        assert c.free_block_count() == 2
        assert c.free_seq("a") == 0               # still referenced by b
        assert c.free_seq("b") == 2               # last ref frees
        assert c.free_block_count() == 4
        c.alloc_seq("c", 7)                       # 4 blocks: pool full
        with pytest.raises(NoFreeBlocks):
            c.alloc_seq("d", 1)
        # growth pressure: c is full at 8 slots (4 blocks x 2)
        c.append_slot("c")                        # slot 8 fits block 4? no:
        with pytest.raises(NoFreeBlocks):
            # 7 filled + 1 appended = 8 = capacity; next needs a block
            c.append_slot("c")
    finally:
        c.close()


# ------------------------------------------------------ scheduler units
def test_scheduler_admission_preempt_order():
    s = IterationScheduler(max_num_seqs=2, max_prefill_tokens=8,
                           max_model_len=16)
    with pytest.raises(ValueError):
        s.add(Sequence("x", list(range(9)), _SP()))          # prompt cap
    with pytest.raises(ValueError):
        s.add(Sequence("x", [1, 2], _SP(max_tokens=15)))     # ctx cap
    a = Sequence("a", [1, 2], _SP(max_tokens=4))
    b = Sequence("b", [1, 2, 3], _SP(max_tokens=4))
    s.add(a)
    s.add(b)
    plan = s.plan(blocks_free=10, blocks_needed_fn=lambda n: 1)
    assert plan.prefill is a                    # FIFO admission
    s.start_running(plan.prefill)
    # no blocks -> no admission, decode only
    plan = s.plan(blocks_free=0, blocks_needed_fn=lambda n: 1)
    assert plan.prefill is None and plan.decode == [a]
    s.start_running(b)
    b.arrival = a.arrival + 1
    assert s.victim() is b                      # latest arrival evicts
    a_out_before = list(a.output)
    b.output = [7, 8]
    s.preempt(b)
    assert b.prompt[-2:] == [7, 8] and b.output == []
    assert s.waiting[0] is b                    # re-queued at the front
    assert b.generated == 2                     # budget survives preempt
    assert a.output == a_out_before


# ------------------------------------------------------- engine proper
@pytest.mark.parametrize("model", ["gpt2:tiny", "llama:tiny"])
def test_engine_matches_full_forward_oracle(model):
    eng = LLMEngine(tiny_cfg(model=model))
    try:
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 100, size=7).tolist()
        got = eng.generate(prompt, SamplingParams(max_tokens=8))
        assert got == oracle_decode(eng, prompt, 8)
    finally:
        eng.shutdown()


def test_continuous_batching_concurrent_equals_solo(engine):
    sp = SamplingParams(max_tokens=6)
    solo = engine.generate([7, 8, 9], sp)
    streams = [engine.submit([7, 8, 9], sp) for _ in range(4)]
    outs = [s.tokens() for s in streams]
    assert all(o == solo for o in outs)
    st = engine.stats()
    # batched: 4 concurrent sequences took far fewer than 4x6 steps
    assert st["decode_steps"] < 4 * 6 + 6


def test_mixed_prompts_interleave_and_finish(engine):
    rng = np.random.default_rng(2)
    jobs = [(rng.integers(1, 100, size=rng.integers(3, 12)).tolist(),
             int(rng.integers(2, 9))) for _ in range(6)]
    streams = [engine.submit(p, SamplingParams(max_tokens=n))
               for p, n in jobs]
    outs = [s.tokens() for s in streams]
    for (p, n), o in zip(jobs, outs):
        assert len(o) == n
        assert o == oracle_decode(engine, p, n)


def test_preemption_exact_resume_and_counters():
    eng = LLMEngine(tiny_cfg(num_blocks=6, block_size=4, max_model_len=32,
                             max_prefill_tokens=16,
                             prefill_len_buckets=(16, 32)))
    try:
        sp = SamplingParams(max_tokens=12)
        streams = [eng.submit([1 + i, 2, 3], sp) for i in range(3)]
        outs = [s.tokens() for s in streams]
        assert eng.stats()["preemptions"] >= 1
        assert all(len(o) == 12 for o in outs)
        # identical to a pressure-free engine: preemption is invisible
        big = LLMEngine(tiny_cfg(num_blocks=64, block_size=4,
                                 max_model_len=32, max_prefill_tokens=16,
                                 prefill_len_buckets=(16, 32)))
        try:
            for i, o in enumerate(outs):
                assert o == big.generate([1 + i, 2, 3], sp)
        finally:
            big.shutdown()
        # all blocks returned after the storm
        assert eng.cache.free_block_count() == 6
    finally:
        eng.shutdown()


def test_bounded_compiles_across_request_storm(engine):
    rng = np.random.default_rng(3)
    for _ in range(3):
        streams = [engine.submit(
            rng.integers(1, 100, size=rng.integers(3, 15)).tolist(),
            SamplingParams(max_tokens=int(rng.integers(2, 7))))
            for _ in range(5)]
        for s in streams:
            s.tokens()
    # every program is a (kind, bucket) pair; the storm must not exceed
    # the configured bucket space
    cfg = engine.cfg
    assert engine.runner.compiles <= \
        len(cfg.prefill_len_buckets) + len(cfg.decode_batch_buckets)


def test_oversize_prompt_fails_cleanly(engine):
    stream = engine.submit(list(range(60)),
                           SamplingParams(max_tokens=8))
    with pytest.raises(RuntimeError, match="max_prefill_tokens"):
        stream.tokens()


# ------------------------------------------- prefill/decode handoff
def test_handoff_attaches_without_recompute():
    """A decode engine adopts a remotely-prefilled block table via the
    PR-4 streamed data plane and continues the stream EXACTLY — its own
    prefill counter stays at zero (ISSUE 6 acceptance)."""
    cfg = tiny_cfg(model="llama:tiny")
    pre, dec = LLMEngine(cfg), LLMEngine(cfg)
    try:
        prompt = [5, 9, 13, 21, 34, 2, 11]
        sp = SamplingParams(max_tokens=9)
        ref = pre.generate(prompt, sp)
        man = pre.prefill_remote(prompt, sp)
        assert len(man["blocks"]) == pre.cache.blocks_needed(len(prompt))
        assert man["addr"].startswith("tcp://")
        got = dec.attach(man, sp).tokens()
        assert got == ref
        assert dec.prefill_steps == 0           # no recompute, ever
        assert dec.decode_steps > 0
        # the prefill side released its working blocks after export
        assert pre.cache.free_block_count() == cfg.num_blocks
    finally:
        pre.shutdown()
        dec.shutdown()


def test_attach_respects_batch_capacity_and_cancel():
    """Adopting more manifests than max_num_seqs must queue the excess
    (not wedge the decode bucket), and an attached stream's cancel()
    frees its blocks."""
    cfg = tiny_cfg(max_num_seqs=2, decode_batch_buckets=(1, 2))
    pre, dec = LLMEngine(cfg), LLMEngine(cfg)
    try:
        sp = SamplingParams(max_tokens=6)
        mans = [pre.prefill_remote([3 + i, 5, 7], sp) for i in range(5)]
        streams = [dec.attach(m, sp) for m in mans]
        outs = [s.tokens() for s in streams]
        assert all(len(o) == 6 for o in outs)
        assert dec.prefill_steps == 0
        # cancel an attached-but-unread stream: blocks come back
        man = pre.prefill_remote([9, 9, 9], sp)
        s = dec.attach(man, sp)
        s.cancel()
        deadline = time.monotonic() + 10
        while dec.cache.used_block_count() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert dec.cache.used_block_count() == 0
    finally:
        pre.shutdown()
        dec.shutdown()


def test_handoff_rejects_geometry_mismatch():
    pre = LLMEngine(tiny_cfg())
    dec = LLMEngine(tiny_cfg(block_size=4))
    try:
        man = pre.prefill_remote([1, 2, 3], SamplingParams(max_tokens=2))
        with pytest.raises(ValueError, match="geometry"):
            dec.attach(man, SamplingParams(max_tokens=2))
    finally:
        pre.shutdown()
        dec.shutdown()


# ------------------------------------------- the pool lives on the device
def host_pool_decode(cfg, params, prompt, n):
    """The semantics the device pool replaced, kept as the reference: a
    numpy pool written on the host around ``forward_prefill`` /
    ``forward_decode`` (greedy, one sequence)."""
    import jax
    mod, mcfg = resolve_model(cfg)
    prefill = jax.jit(lambda p, t, last: mod.forward_prefill(
        p, t, mcfg, last_pos=last))
    decode = jax.jit(lambda *a: mod.forward_decode(*a, mcfg))
    bs, maxb = cfg.block_size, cfg.max_blocks_per_seq
    n_kv = getattr(mcfg, "n_kv_head", mcfg.n_head)
    pool = np.zeros(kvmod.device_shape(cfg.num_blocks, mcfg.n_layer, bs,
                                       n_kv, mcfg.head_dim), np.float32)
    f = n_kv * mcfg.head_dim                     # a row's lanes in use

    def write(t, k, v):                          # k, v: (L, KV, D)
        pool[:, 0, table[t // bs], t % bs, :f] = k.reshape(len(k), f)
        pool[:, 1, table[t // bs], t % bs, :f] = v.reshape(len(v), f)

    table = list(range(3, 3 + maxb))             # any distinct blocks
    tb = next(b for b in cfg.prefill_len_buckets if len(prompt) <= b)
    toks = np.zeros((1, tb), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, ks, vs = prefill(params, toks, np.int32(len(prompt) - 1))
    ks, vs = np.asarray(ks, np.float32)[:, 0], np.asarray(vs, np.float32)[:, 0]
    for t in range(len(prompt)):
        write(t, ks[:, t], vs[:, t])
    out = [int(np.argmax(np.asarray(logits)[0]))]
    tables = np.asarray([table], np.int32)
    while len(out) < n:
        at = len(prompt) + len(out) - 1         # position of the last token
        lens = np.asarray([at], np.int32)
        logits, k, v = decode(params, np.asarray([out[-1]], np.int32), lens,
                              pool, tables, lens)
        write(at, np.asarray(k, np.float32)[:, 0],
              np.asarray(v, np.float32)[:, 0])
        out.append(int(np.argmax(np.asarray(logits)[0])))
    return out


@pytest.mark.parametrize("model", ["gpt2:tiny", "llama:tiny"])
def test_device_pool_matches_host_pool_semantics(model):
    """A mixed batch through the engine (pool on the device, written by
    the step's own program) emits what the host-written numpy pool did."""
    eng = LLMEngine(tiny_cfg(model=model))
    try:
        rng = np.random.default_rng(5)
        jobs = [(rng.integers(1, 100, size=k).tolist(), n)
                for k, n in ((3, 9), (11, 5), (17, 12))]
        streams = [eng.submit(p, SamplingParams(max_tokens=n))
                   for p, n in jobs]
        outs = [s.tokens() for s in streams]
        for (p, n), o in zip(jobs, outs):
            assert o == host_pool_decode(eng.cfg, eng.runner.params, p, n)
        assert eng.stats()["kv_host_bytes"] == 0
    finally:
        eng.shutdown()


def _random_pool(cache, seed=0):
    """Every block of the cache set to known random bytes; the copy, in
    the wire format ``(num_blocks,) + block_shape`` as ``cache.blocks()``
    reads the pool back."""
    rng = np.random.default_rng(seed)
    want = rng.standard_normal(
        (cache.num_blocks,) + cache.block_shape).astype(np.float32)
    for b in range(cache.num_blocks):
        cache.load_block(b, want[b].tobytes())
    return want


@pytest.mark.parametrize("model", ["gpt2:tiny", "llama:tiny"])
def test_padded_decode_rows_write_nowhere(model):
    """3 sequences in a bucket of 8: the step writes their 3 slots and
    leaves every other byte of the pool as it was — block 0, slot 0
    included, which the padded rows' tables of zeros name."""
    from ray_tpu.serve.llm.model_runner import ModelRunner
    cfg = tiny_cfg(model=model, num_blocks=16, decode_batch_buckets=(8,),
                   max_num_seqs=8)
    runner = ModelRunner(cfg)
    cache = PagedKVCache(cfg.num_blocks, runner.n_layer, cfg.block_size,
                         runner.n_kv, runner.head_dim)
    before = _random_pool(cache)
    tables = np.zeros((3, cfg.max_blocks_per_seq), np.int32)
    tables[:, :3] = [[5, 6, 7], [9, 2, 11], [12, 13, 1]]
    lens = np.asarray([9, 16, 23], np.int32)     # slots (6,1) (11,0) (1,7)
    logits, k, v = runner.decode(np.asarray([4, 5, 6], np.int32), lens,
                                 cache.pool, tables, lens)
    assert logits.shape == (3, runner.vocab)
    after = cache.blocks()
    want = before.copy()
    for i, (blk, off) in enumerate([(6, 1), (11, 0), (1, 7)]):
        want[blk, :, 0, off] = np.asarray(k, np.float32)[:, i]
        want[blk, :, 1, off] = np.asarray(v, np.float32)[:, i]
    assert not np.array_equal(want, before)
    np.testing.assert_array_equal(after, want)


@pytest.mark.parametrize("t_pad", [16, 32])
def test_prefill_scatter_writes_only_the_real_tokens(t_pad):
    """The padded prompt's tail and the padded table's tail write
    nowhere; numpy and device K/V run the same program, ``write_rows``'
    one form at either width."""
    import jax.numpy as jnp
    cache = PagedKVCache(num_blocks=8, n_layer=2, block_size=4, n_kv=2,
                         head_dim=3)
    before = _random_pool(cache, seed=1)
    host0 = cache.host_bytes
    rng = np.random.default_rng(2)
    ks = rng.standard_normal((2, t_pad, 2, 3)).astype(np.float32)
    vs = rng.standard_normal((2, t_pad, 2, 3)).astype(np.float32)
    cache.alloc_seq("a", 6)                       # 2 blocks of the 4 padded
    table = cache.table("a")
    cache.scatter_prefill("a", jnp.asarray(ks), jnp.asarray(vs), 6)
    assert cache.host_bytes == host0              # device K/V: nothing crossed
    want = before.copy()
    for t in range(6):
        want[table[t // 4], :, 0, t % 4] = ks[:, t]
        want[table[t // 4], :, 1, t % 4] = vs[:, t]
    np.testing.assert_array_equal(cache.blocks(), want)
    cache.scatter_prefill("a", ks, vs, 6)         # numpy: same bytes, counted
    np.testing.assert_array_equal(cache.blocks(), want)
    assert cache.host_bytes == host0 + ks.nbytes + vs.nbytes
    assert cache.block_bytes(table[1]) == want[table[1]].tobytes()


@pytest.mark.parametrize("n_kv,head_dim,lanes", [
    pytest.param(25, 64, 1664, id="1600_lanes_padded"),
    pytest.param(4, 128, 512, id="512_lanes_whole")])
@pytest.mark.parametrize("rows", [1, 8, 32, 512])
def test_write_rows_writes_its_rows_and_drops_the_rest(rows, n_kv, head_dim,
                                                       lanes):
    """``write_rows``, traced and donated as the step programs run it: a
    token's row, a decode batch's 8 or 32 and a prompt's 512, at both
    cells' lane widths.  Every third row is sent out of range (block
    ``num_blocks`` or beyond) and writes nowhere; the others land in
    their slots, K beside V, and every other byte stays."""
    import jax
    import jax.numpy as jnp
    n_layer, bs = 2, 16
    num_blocks = max(4, 2 * rows // bs)
    shape = kvmod.device_shape(num_blocks, n_layer, bs, n_kv, head_dim)
    assert shape == (n_layer, 2, num_blocks, bs, lanes)
    rng = np.random.default_rng(rows)
    before = rng.standard_normal(shape).astype(np.float32)
    slots = rng.permutation(num_blocks * bs)[:rows]   # distinct slots
    blocks, offsets = (slots // bs).astype(np.int32), \
        (slots % bs).astype(np.int32)
    dropped = np.arange(rows) % 3 == 2
    blocks[dropped] = num_blocks + np.arange(dropped.sum()) % 2
    k, v = rng.standard_normal((2, n_layer, rows, n_kv, head_dim)
                               ).astype(np.float32)
    after = np.asarray(jax.jit(kvmod.write_rows, donate_argnums=0)(
        jnp.asarray(before), blocks, offsets, k, v))
    want, f = before.copy(), n_kv * head_dim
    for r in np.flatnonzero(~dropped):
        want[:, 0, blocks[r], offsets[r]] = 0.0       # the padding's lanes
        want[:, 1, blocks[r], offsets[r]] = 0.0
        want[:, 0, blocks[r], offsets[r], :f] = k[:, r].reshape(n_layer, f)
        want[:, 1, blocks[r], offsets[r], :f] = v[:, r].reshape(n_layer, f)
    np.testing.assert_array_equal(after, want)


@pytest.mark.parametrize("n_kv,head_dim", [(25, 64), (4, 128), (2, 3)])
def test_an_exported_block_loads_bit_identical_into_another_cache(n_kv,
                                                                  head_dim):
    """The wire format did not follow the device's: a block is
    ``(L, 2, bs, KV, D)`` without lane padding, ``block_nbytes`` is that
    shape's bytes, and ``block_bytes`` -> ``load_block`` into another
    cache (another block id) gives the same bytes back."""
    n_layer, bs = 3, 16
    src = PagedKVCache(4, n_layer, bs, n_kv, head_dim)
    dst = PagedKVCache(6, n_layer, bs, n_kv, head_dim)
    assert src.block_shape == (n_layer, 2, bs, n_kv, head_dim)
    assert src.block_nbytes == dst.block_nbytes == \
        n_layer * 2 * bs * n_kv * head_dim * 4
    assert src.lane_pad_bytes == 4 * n_layer * 2 * bs * 4 * \
        (-(n_kv * head_dim) % 128)
    want = _random_pool(src, seed=4)
    others = dst.blocks()
    raw = src.block_bytes(2)
    assert raw == want[2].tobytes() and len(raw) == src.block_nbytes
    dst.load_block(5, raw)
    assert dst.block_bytes(5) == raw
    got = dst.blocks()
    np.testing.assert_array_equal(got[5], want[2])
    np.testing.assert_array_equal(got[:5], others[:5])    # untouched


def test_kv_host_bytes_counts_only_exported_and_imported_blocks():
    cfg = tiny_cfg()
    pre, dec = LLMEngine(cfg), LLMEngine(cfg)
    try:
        sp = SamplingParams(max_tokens=7)
        prompt = list(range(2, 21))               # 19 tokens: 3 blocks
        pre.generate(prompt, sp)
        dec.generate([4, 5], sp)
        assert pre.stats()["decode_steps"] > 0
        assert pre.stats()["kv_host_bytes"] == 0
        assert dec.stats()["kv_host_bytes"] == 0
        man = pre.prefill_remote(prompt, sp)
        moved = len(man["blocks"]) * pre.cache.block_nbytes
        assert len(man["blocks"]) == 3
        assert pre.stats()["kv_host_bytes"] == moved
        assert dec.attach(man, sp).tokens() == pre.generate(prompt, sp)
        assert dec.stats()["kv_host_bytes"] == moved
        assert pre.stats()["kv_host_bytes"] == moved
    finally:
        pre.shutdown()
        dec.shutdown()


def test_pool_programs_alias_the_pool_to_their_result():
    """The decode step and the scatter take the pool donated: the
    lowered programs alias argument 0 to result 0 (a copy of the pool
    a step would be the transfer this design removed, in another place)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.serve.llm.model_runner import ModelRunner
    cfg = tiny_cfg(decode_batch_buckets=(4,))
    runner = ModelRunner(cfg)
    S = jax.ShapeDtypeStruct
    pool = S(kvmod.device_shape(cfg.num_blocks, runner.n_layer,
                                cfg.block_size, runner.n_kv,
                                runner.head_dim), jnp.float32)
    held = {"kv": pool}
    i32 = lambda *shape: S(shape, jnp.int32)      # noqa: E731
    kv = S((runner.n_layer, 32, runner.n_kv, runner.head_dim), jnp.float32)
    lowered = {
        "decode": runner._decode.lower(
            held, runner.params, i32(4), i32(4),
            i32(4, cfg.max_blocks_per_seq), i32(4), i32(), i32(4), i32(4)),
        "scatter": kvmod._programs().scatter_prefill.lower(
            held, i32(4), kv, kv, i32()),
    }
    for name, low in lowered.items():
        assert "tf.aliasing_output = 0" in \
            low.as_text().split("%arg1")[0], name
        compiled = low.compile()
        assert "input_output_alias={ {" in compiled.as_text(), name
        assert compiled.memory_analysis().alias_size_in_bytes == \
            int(np.prod(pool.shape)) * 4, name


def test_attach_from_another_thread_while_the_loop_decodes(caplog):
    """attach loads blocks into the pool from its caller's thread while
    the engine's loop donates the same pool step after step: no program
    sees a donated array, and every stream equals its solo run."""
    import logging
    import sys
    import threading
    cfg = tiny_cfg(max_num_seqs=4)
    pre, dec = LLMEngine(cfg), LLMEngine(cfg)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        sp = SamplingParams(max_tokens=24)
        prompts = [[3 + i, 5, 7, 11, 13, 17, 19, 23, 29] for i in range(6)]
        solo = [pre.generate(p, sp) for p in prompts]
        mans = [pre.prefill_remote(p, sp) for p in prompts[1:]]
        outs, errs = {}, []

        def adopt(i, man):
            try:
                outs[i] = dec.attach(man, sp).tokens()
            except Exception as e:  # noqa: BLE001 - reported below
                errs.append(repr(e))

        with caplog.at_level(logging.ERROR):
            first = dec.submit(prompts[0], sp)    # the loop is decoding
            threads = [threading.Thread(target=adopt, args=(i + 1, m))
                       for i, m in enumerate(mans)]
            for t in threads:
                t.start()
            outs[0] = first.tokens()
            for t in threads:
                t.join(timeout=120 * time_scale())
            assert not any(t.is_alive() for t in threads)
        assert not errs, errs
        assert not [r for r in caplog.records
                    if "step failed" in r.getMessage()
                    or "deleted" in r.getMessage()]
        assert [outs[i] for i in range(6)] == solo
        assert dec.prefill_steps == 1 and dec.decode_steps > 0
    finally:
        sys.setswitchinterval(old)
        pre.shutdown()
        dec.shutdown()


def test_the_benchmark_harness_calls_keep_working():
    """What ``perfbench/jobs/serve.py`` does to an engine it does not
    own the code of: ``pool.fill(0)``, ``runner.decode`` twice on the
    same ``cache.pool`` expression (the harness rebinds nothing),
    ``scatter_prefill`` and ``write_token`` with numpy K/V after a step
    that already wrote them."""
    eng = LLMEngine(tiny_cfg(), start=False)
    try:
        runner, cache = eng.runner, eng.cache
        cache.pool.fill(0)
        assert not cache.blocks().any()
        maxb = eng.cfg.max_blocks_per_seq
        for _ in range(2):                        # _warm_programs
            runner.decode(np.zeros(4, np.int32), np.zeros(4, np.int32),
                          cache.pool, np.zeros((4, maxb), np.int32),
                          np.ones(4, np.int32))
        cache.pool.fill(0)
        prompt = list(range(1, 12))               # check_logits
        cache.alloc_seq("chk", len(prompt))
        logits, ks, vs = runner.prefill(prompt)
        assert logits.shape == (runner.vocab,)
        cache.scatter_prefill("chk", np.asarray(ks, np.float32),
                              np.asarray(vs, np.float32), len(prompt))
        seq = prompt + [int(np.argmax(logits))]
        blk, off, _ = cache.append_slot("chk")
        tables = np.zeros((1, maxb), np.int32)
        table = cache.table("chk")
        tables[0, :len(table)] = table
        at = np.asarray([len(seq) - 1], np.int32)
        lg, ks, vs = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                   cache.pool, tables, at)
        assert lg[0].shape == (runner.vocab,)
        stepped = cache.blocks()
        assert stepped[blk, :, :, off].any()      # the step wrote the slot
        cache.write_token(blk, off, np.asarray(ks[:, 0], np.float32),
                          np.asarray(vs[:, 0], np.float32))
        np.testing.assert_array_equal(cache.blocks(), stepped)
        assert eng.stats()["kv_host_bytes"] > 0   # the harness's numpy K/V
        assert seq[-1] == oracle_decode(eng, prompt, 1)[0]
        cache.free_seq("chk")
        _the_harness_labels_every_iteration(eng)
    finally:
        eng.shutdown()


def _the_harness_labels_every_iteration(eng):
    """``Served._instrument`` and ``_wait_idle``: ``eng.step`` and
    ``runner.decode`` are wrapped, an iteration is a decode step if
    ``decode_steps`` rose over it (and then it called ``runner.decode``
    once: a ``pb.step`` is a ``pb.decode`` only if it holds a
    ``pb.decode.run``), and after the traffic ``running`` and ``waiting``
    reach 0.  An iteration that only reads the step in flight is neither
    a decode nor a prefill, and still says there was work."""
    runner, log, calls = eng.runner, [], []
    step, decode = eng.step, runner.decode

    def timed_step():
        before, n = eng.stats(), len(calls)
        ran = step()
        after = eng.stats()
        log.append((ran, after["decode_steps"] - before["decode_steps"],
                    after["prefill_steps"] - before["prefill_steps"],
                    len(calls) - n, sum(after["decode_drains"].values())
                    - sum(before["decode_drains"].values())))
        return ran

    def spanned(*args, **kwargs):
        calls.append(len(args[0]))
        return decode(*args, **kwargs)

    eng.step, runner.decode = timed_step, spanned
    jobs = [(PROMPTS[0], 9), (PROMPTS[1], 5)]
    streams = [eng.submit(p, SamplingParams(max_tokens=n)) for p, n in jobs]
    while eng.step():
        pass
    stats = eng.stats()
    assert stats["running"] == 0 and stats["waiting"] == 0
    assert [len(s.tokens()) for s in streams] == [9, 5]
    assert all(ran for ran, *_ in log[:-1]) and not log[-1][0]
    for _, decodes, prefills, called, drained in log:
        assert (decodes, prefills) in ((1, 0), (0, 1), (0, 0))
        assert called == decodes
    # no row-step is spent on a sequence known to end by length
    assert calls == [2] * 4 + [1] * 4
    only_drained = [e for e in log[:-1] if e[1:4] == (0, 0, 0)]
    assert [e[4] for e in only_drained] == [1]    # the tail, and it ran
    assert stats["decode_steps_ahead"] == 7 and \
        stats["decode_drains"]["tail"] == 1


# ------------------------------------ the weights are cast once (ISSUE 31)
SERVED = ["gpt2:tiny", "llama:tiny", "llama:tiny-moe"]


def _stored_tree(cfg):
    """The tree ``init_params`` makes: float32 under a bf16-computing
    preset, what a caller like the benchmark's harness hands over."""
    import jax
    mod, mcfg = resolve_model(cfg)
    assert mcfg.param_dtype != mcfg.dtype
    return mod, mcfg, mod.init_params(jax.random.key(3), mcfg)


def _second_holdings(mod, tree):
    """key -> shape of the tables ``serving_params`` holds a second time:
    those a module with a tied head names whose rows are no whole lanes."""
    from ray_tpu.models._common import LANES
    return {held: (tree[name].shape[0],
                   -(-tree[name].shape[1] // LANES) * LANES)
            for name, held in getattr(mod, "ROW_TABLES", {}).items()
            if tree[name].shape[1] % LANES}


def _weight_converts(jaxpr, shapes):
    """convert_element_type equations, sub-programs included, that narrow
    a float32 operand of one of ``shapes``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            a = eqn.invars[0].aval
            if a.shape in shapes and a.dtype == np.float32 \
                    and eqn.outvars[0].aval.dtype != np.float32:
                found.append(a.shape)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _weight_converts(sub, shapes)
    return found


@pytest.mark.parametrize("model", SERVED)
def test_step_programs_convert_no_weight(model):
    """Handed the runner's tree, decode and prefill hold no convert of a
    weight; handed the stored tree, the same programs hold one a cast
    leaf (the detector can see them).  The leaves a module keeps wide
    are as stored, all others in the compute type, and the token table of
    a tied head at a width of no whole lanes (gpt2:tiny's 64) is there a
    second time."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.serve.llm.model_runner import ModelRunner
    cfg = tiny_cfg(model=model, decode_batch_buckets=(4,))
    mod, mcfg, stored = _stored_tree(cfg)
    runner = ModelRunner(cfg, params=stored)
    flat = jax.tree_util.tree_flatten_with_path(runner.params)[0]
    added = _second_holdings(mod, stored)
    assert bool(added) == (model == "gpt2:tiny")
    assert len(flat) == len(jax.tree_util.tree_leaves(stored)) + len(added)
    assert {key: runner.params[key].shape for key in added} == added
    wide = 0
    for path, leaf in flat:
        keep = any(k.key in mod.WIDE_PARAMS for k in path)
        wide += keep
        assert leaf.dtype == (jnp.float32 if keep else mcfg.dtype), path
    assert 0 < wide < len(flat)
    S = jax.ShapeDtypeStruct
    i32 = lambda *shape: S(shape, jnp.int32)      # noqa: E731
    pool = S(kvmod.device_shape(cfg.num_blocks, runner.n_layer,
                                cfg.block_size, runner.n_kv,
                                runner.head_dim), jnp.float32)
    # a stacked leaf is sliced to one layer inside the scan
    shapes = {x.shape for x in jax.tree_util.tree_leaves(stored)} \
        | {x.shape[1:] for x in jax.tree_util.tree_leaves(stored["blocks"])}
    for tree, n_cast in ((runner.params, 0),
                         (stored, len(flat) - len(added) - wide)):
        decode = jax.make_jaxpr(runner._decode)(
            {"kv": pool}, tree, i32(4), i32(4),
            i32(4, cfg.max_blocks_per_seq), i32(4), i32(), i32(4), i32(4))
        prefill = jax.make_jaxpr(runner._prefill)(None, tree, i32(1, 32),
                                                  i32())
        for jaxpr in (decode, prefill):
            got = _weight_converts(jaxpr.jaxpr, shapes)
            assert (len(got) >= n_cast) if n_cast else not got, got


@pytest.mark.parametrize("model", SERVED)
def test_prepared_tree_gives_the_stored_trees_logits(model):
    """Prefill and 4 decode steps of an engine handed the float32 tree
    against the models' own forwards over that float32 tree: the same
    bits.  A convert is exact and each matmul took bf16 operands
    already, so only the place of the conversion moved."""
    import jax
    cfg = tiny_cfg(model=model)
    mod, mcfg, stored = _stored_tree(cfg)
    pure_prefill = jax.jit(lambda p, t, last: mod.forward_prefill(
        p, t, mcfg, last_pos=last))
    pure_decode = jax.jit(lambda *a: mod.forward_decode(*a, mcfg))
    eng = LLMEngine(cfg, params=stored, start=False)
    try:
        runner, cache = eng.runner, eng.cache
        prompt = np.random.default_rng(7).integers(1, 100, 11).tolist()
        cache.alloc_seq("chk", len(prompt))
        logits, ks, vs = runner.prefill(prompt)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(prompt)] = prompt
        want, wk, wv = pure_prefill(stored, toks, np.int32(len(prompt) - 1))
        np.testing.assert_array_equal(logits, np.asarray(want)[0])
        np.testing.assert_array_equal(np.asarray(ks, np.float32),
                                      np.asarray(wk, np.float32)[:, 0])
        cache.scatter_prefill("chk", ks, vs, len(prompt))
        seq = list(prompt)
        maxb = cfg.max_blocks_per_seq
        for _ in range(4):
            seq.append(int(np.argmax(logits)))
            cache.append_slot("chk")
            tables = np.zeros((1, maxb), np.int32)
            table = cache.table("chk")
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            tok = np.asarray([seq[-1]], np.int32)
            before = np.asarray(cache.pool[:])
            want, wk, wv = pure_decode(stored, tok, at, before, tables, at)
            lg, k, v = runner.decode(tok, at, cache.pool, tables, at)
            logits = lg[0]
            np.testing.assert_array_equal(logits, np.asarray(want)[0])
            np.testing.assert_array_equal(np.asarray(v, np.float32)[:, :1],
                                          np.asarray(wv, np.float32))
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model", SERVED)
def test_a_tree_in_its_serving_type_is_taken_as_it_is(model):
    """Equal stored and compute types (a prepared tree handed on; bf16
    training state, norms included): the runner's leaves are the
    caller's own and no cast is built.  A prepared tree comes back as the
    object it is; training state gains the table its gather wants, and
    ``bytes_out - bytes_in`` of the prepare span is that table's bytes."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import _common
    from ray_tpu.serve.llm.model_runner import ModelRunner
    cfg = tiny_cfg(model=model)
    mod, mcfg, stored = _stored_tree(cfg)
    first = ModelRunner(cfg, params=stored)
    assert first.span_s["llm.weights.prepare"][0] == 1
    trained = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), stored)
    built = _common._cast_leaves._cache_size()
    assert ModelRunner(cfg, params=first.params).params is first.params
    for tree in (first.params, trained):
        runner = ModelRunner(cfg, params=tree)
        added = {key: shape
                 for key, shape in _second_holdings(mod, tree).items()
                 if key not in tree}
        assert set(runner.params) == set(tree) | set(added)
        for mine, theirs in zip(
                jax.tree_util.tree_leaves({k: runner.params[k] for k in tree}),
                jax.tree_util.tree_leaves(tree)):
            assert mine is theirs
        assert runner.param_bytes - _common.tree_bytes(tree) == sum(
            rows * width * 2 for rows, width in added.values())
        assert runner.span_s["llm.weights.prepare"][0] == 1
    assert _common._cast_leaves._cache_size() == built


@pytest.mark.parametrize("width", [64, 128, 192, 256])
def test_a_tied_token_table_is_held_a_second_time_for_its_gather(width):
    """GPT-2's head is its embedding, so ``wte`` has two uses.  At a width
    of no whole lanes the serving tree holds the table a second time: the
    same rows bit for bit, in ``cfg.dtype``, zeros behind each up to whole
    lanes (what the device holds in row order, so the gather of a step's
    rows reads it in place and the head ``wte`` in place:
    tests/test_chip_compile.py), beside the leaves it had.  ``_embed``,
    the prefill's and the decode step's logits are the same bits from the
    serving tree and from the stored one.  At whole lanes one leaf serves
    both uses and the tree comes back as it is."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from ray_tpu.models import _common, gpt2
    cfg = dataclasses.replace(gpt2.tiny(), n_embd=width)
    assert cfg.param_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    stored = gpt2.init_params(jax.random.key(5), cfg)
    served = _common.serving_params(stored, cfg.dtype, gpt2.WIDE_PARAMS,
                                    gpt2.ROW_TABLES)
    assert _common.serving_params(served, cfg.dtype, gpt2.WIDE_PARAMS,
                                  gpt2.ROW_TABLES) is served
    assert gpt2.ROW_TABLES == {"wte": "wte_rows"}
    if width % _common.LANES == 0:
        assert set(served) == set(stored)
    else:
        lanes = -(-width // _common.LANES) * _common.LANES
        assert set(served) == set(stored) | {"wte_rows"}
        rows = served["wte_rows"]
        assert rows.shape == (cfg.vocab_size, lanes)
        assert rows.dtype == served["wte"].dtype == cfg.dtype
        np.testing.assert_array_equal(
            np.asarray(rows[:, :width], np.float32),
            np.asarray(served["wte"], np.float32))
        assert not np.asarray(rows[:, width:], np.float32).any()
        assert _common.tree_bytes(served) - _common.tree_bytes(
            {k: served[k] for k in stored}) == cfg.vocab_size * lanes * 2

    tokens = np.random.default_rng(width).integers(
        0, cfg.vocab_size, (1, 16)).astype(np.int32)
    at = np.asarray([5, 9], np.int32)
    pool = jnp.zeros(kvmod.device_shape(4, cfg.n_layer, 8, cfg.n_head,
                                        cfg.head_dim), jnp.float32)
    tables = np.zeros((2, 2), np.int32)
    embed = jax.jit(lambda p: gpt2._embed(p, tokens, jnp.arange(16), cfg))
    prefill = jax.jit(lambda p: gpt2.forward_prefill(
        p, tokens, cfg, last_pos=jnp.int32(11))[0])
    decode = jax.jit(lambda p: gpt2.forward_decode(
        p, tokens[0, :2], at, pool, tables, at, cfg)[0])
    for program in (embed, prefill, decode):
        got, want = program(served), program(stored)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("model", ["llama:tiny", "llama:tiny-moe",
                                   "falcon_h1:tiny", "lfm2:tiny"])
def test_a_family_that_names_no_table_gets_the_tree_it_got(model):
    """Only a module that says its head is tied (``ROW_TABLES``) has a
    table held twice.  Llama and Falcon-H1 have a head of their own;
    LFM2's tied table is 2,048 wide, whole lanes, and its step copies
    nothing (tests/test_chip_compile.py): a tree of theirs in its serving
    type comes back as the object it is, through the runner too."""
    import jax
    from ray_tpu.models._common import serving_params
    from ray_tpu.serve.llm.model_runner import ModelRunner
    cfg = tiny_cfg(model=model)
    mod, mcfg = resolve_model(cfg)
    assert not hasattr(mod, "ROW_TABLES")
    first = ModelRunner(cfg, params=mod.init_params(jax.random.key(3), mcfg))
    assert serving_params(first.params, mcfg.dtype, mod.WIDE_PARAMS,
                          None) is first.params
    again = ModelRunner(cfg, params=first.params)
    assert again.params is first.params
    assert again.param_bytes == first.param_bytes


@pytest.mark.parametrize("param_dtype", ["float32", "bfloat16"])
def test_gpt2_embeds_from_a_stored_tree_as_it_did(param_dtype):
    """``_embed`` over a tree with no second holding, training's and
    ``forward``'s, is the program it was: the rows of ``wte`` and of
    ``wpe`` gathered and added, nothing cut off (the XL training step's
    digest on the described v5e: tests/test_chip_compile.py)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt2
    cfg = dataclasses.replace(gpt2.tiny(), param_dtype=jnp.dtype(param_dtype))
    stored = gpt2.init_params(jax.random.key(5), cfg)
    tokens, positions = jnp.zeros((2, 16), jnp.int32), jnp.arange(16)

    def plain(p):
        return p["wte"].astype(cfg.dtype)[tokens] \
            + p["wpe"].astype(cfg.dtype)[positions]

    def embed(p):
        return gpt2._embed(p, tokens, positions, cfg)

    assert str(jax.make_jaxpr(embed)(stored)) == \
        str(jax.make_jaxpr(plain)(stored))


@pytest.mark.parametrize("model", SERVED)
def test_weights_are_prepared_once_and_counted(model):
    import jax
    eng = LLMEngine(tiny_cfg(model=model))
    try:
        eng.generate(list(range(1, 9)), SamplingParams(max_tokens=20))
        stats = eng.stats()
        assert stats["prefill_steps"] + stats["decode_steps"] >= 20
        assert stats["span_s"]["llm.weights.prepare"][0] == 1
        leaves = jax.tree_util.tree_leaves(eng.runner.params)
        assert stats["param_bytes"] == sum(x.nbytes for x in leaves)
        # float32 as stored; every matrix is a cast leaf
        stored = sum(x.size * 4 for x in leaves)
        assert stored / 2 < stats["param_bytes"] < 0.6 * stored
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model", SERVED)
def test_weights_through_the_shm_plane_serve_the_same_tokens(model):
    """The plane stores what ``init_params`` made; an engine that
    published it, one that attached and one that loaded privately serve
    the same tokens from trees in the serving type."""
    import jax
    sp = SamplingParams(max_tokens=8)
    prompt = list(range(3, 14))
    engines = [LLMEngine(tiny_cfg(model=model, seed=31, share_weights=share))
               for share in (True, True, False)]
    try:
        assert engines[0].runner.weights_key and engines[1].runner.weights_key
        outs = [eng.generate(prompt, sp) for eng in engines]
        assert outs[0] == outs[1] == outs[2]
        for eng in engines:
            assert eng.stats()["param_bytes"] == \
                engines[2].stats()["param_bytes"]
            for a, b in zip(jax.tree_util.tree_leaves(eng.runner.params),
                            jax.tree_util.tree_leaves(
                                engines[2].runner.params)):
                assert a.dtype == b.dtype
    finally:
        for eng in engines:
            eng.shutdown()


# ------------------------------------------------------- weights plane
# ----------------------- a greedy token is chosen on the device (ISSUE 33)
FAMILIES = ["gpt2:tiny", "llama:tiny", "falcon_h1:tiny"]
PROMPTS = [list(range(3, 14)), list(range(20, 27)), list(range(40, 59))]
SAMPLED = SamplingParams(max_tokens=10, temperature=0.8, top_k=5, seed=7)


def _twin_head(cfg):
    """The model's own weights with the head's second half of the
    vocabulary a copy of its first: every logit has an equal twin, so
    each row's maximum is a tie.  (the tree, half the vocabulary)"""
    import jax
    mod, mcfg = resolve_model(cfg)
    params = mod.init_params(jax.random.key(cfg.seed), mcfg)
    half = mcfg.vocab_size // 2
    if "lm_head" in params:                     # (E, V), untied
        kernel = params["lm_head"]["kernel"]
        params["lm_head"] = {
            "kernel": kernel.at[:, half:2 * half].set(kernel[:, :half])}
    else:                                       # gpt2: the embedding, (V, E)
        wte = params["wte"]
        params["wte"] = wte.at[half:2 * half].set(wte[:half])
    return params, half


def _prefill_and_step(eng, every_row: bool):
    """PROMPTS prefilled into the engine's cache one by one, then one
    decode step over the three of them in the bucket of 4: the first
    result of each call, as the default call gives it or (``every_row``)
    as ``logit_rows`` naming every row does."""
    runner, cache = eng.runner, eng.cache
    firsts = []
    for i, prompt in enumerate(PROMPTS):
        cache.alloc_seq(f"s{i}", len(prompt))
        first, ks, vs = runner.prefill(
            prompt, **({"logit_rows": (0,)} if every_row else {}))
        cache.scatter_prefill(f"s{i}", ks, vs, len(prompt))
        firsts.append(first)
    tables = np.zeros((len(PROMPTS), eng.cfg.max_blocks_per_seq), np.int32)
    for i in range(len(PROMPTS)):
        cache.append_slot(f"s{i}")
        table = cache.table(f"s{i}")
        tables[i, :len(table)] = table
    lens = np.asarray([len(p) for p in PROMPTS], np.int32)
    step, _, _ = runner.decode(
        np.asarray([7, 8, 9], np.int32), lens, cache.pool, tables, lens,
        **({"logit_rows": range(len(PROMPTS))} if every_row else {}))
    return firsts, step


def _both_calls(cfg, params=None):
    """(default call's results, ``logit_rows`` call's results) of the same
    inputs, each on an engine of its own: a step moves what a cache holds."""
    out = []
    for every_row in (False, True):
        eng = LLMEngine(cfg, params=params, start=False)
        try:
            out.append(_prefill_and_step(eng, every_row))
        finally:
            eng.shutdown()
    return out


@pytest.mark.parametrize("model", FAMILIES)
def test_the_default_call_still_returns_all_logits_on_the_host(model):
    eng = LLMEngine(tiny_cfg(model=model), start=False)
    try:
        firsts, step = _prefill_and_step(eng, every_row=False)
        vocab = eng.runner.vocab
    finally:
        eng.shutdown()
    for logits, shape in [(f, (vocab,)) for f in firsts] + \
            [(step, (len(PROMPTS), vocab))]:
        assert type(logits) is np.ndarray and logits.dtype == np.float32
        assert logits.shape == shape and np.isfinite(logits).all()


@pytest.mark.parametrize("model", FAMILIES)
def test_step_ids_are_np_argmax_of_the_default_calls_logits(model):
    """Ties included (every maximum has a twin half a vocabulary on: the
    first is taken), and the row padded up to the bucket is not among
    them."""
    from ray_tpu.serve.llm.model_runner import Chosen
    cfg = tiny_cfg(model=model)
    params, half = _twin_head(cfg)
    (firsts, step), (chosen_firsts, chosen_step) = _both_calls(cfg, params)
    for logits, chosen in zip(firsts + [step], chosen_firsts + [chosen_step]):
        logits = np.atleast_2d(logits)
        assert type(chosen) is Chosen and chosen.ids.dtype == np.int32
        assert chosen.ids.shape == (len(logits),)
        np.testing.assert_array_equal(chosen.ids, np.argmax(logits, -1))
        assert sorted(chosen.logits) == list(range(len(logits)))
        for row, pulled in chosen.logits.items():
            np.testing.assert_array_equal(pulled, logits[row])
        np.testing.assert_array_equal(logits[:, :half],
                                      logits[:, half:2 * half])
        assert (chosen.ids < half).all()


def _chosen_on_the_host(eng):
    """The engine's runner answers its loop from the default call alone:
    every row's logits pulled, every id ``ModelRunner.sample``'s at
    temperature 0 (``np.argmax``)."""
    from ray_tpu.serve.llm.model_runner import Chosen, ModelRunner
    runner = eng.runner
    prefill, decode = runner.prefill, runner.decode
    greedy = SamplingParams()

    def on_host(logits):
        rows = np.atleast_2d(logits)
        ids = [ModelRunner.sample(row, greedy, 0) for row in rows]
        return Chosen(np.asarray(ids, np.int32), dict(enumerate(rows)))

    def host_prefill(token_ids, *, logit_rows):
        logits, ks, vs = prefill(token_ids)
        return on_host(logits), ks, vs

    # the loop enqueues a step and pulls it later: every row's logits
    # are named at the enqueue, and sampled here at the pull
    def host_decode(*args, logit_rows, **ahead):
        return decode(*args, logit_rows=range(len(args[0])), **ahead)

    def host_pull(step):
        chosen = pull(step)
        return on_host(np.stack([chosen.logits[i]
                                 for i in range(len(chosen.ids))]))

    pull = runner.pull_step
    runner.prefill, runner.decode = host_prefill, host_decode
    runner.pull_step = host_pull


def _scripted(cfg, requests, on_host=False):
    """(each request's tokens, the engine's stats) of ``requests``
    submitted together and stepped by hand to the end."""
    eng = LLMEngine(cfg, start=False)
    try:
        if on_host:
            _chosen_on_the_host(eng)
        streams = [eng.submit(prompt, sp) for prompt, sp in requests]
        while eng.step():
            pass
        return [s.tokens() for s in streams], eng.stats()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model", FAMILIES)
def test_greedy_loop_emits_what_sample_on_pulled_logits_emits(model):
    cfg = tiny_cfg(model=model)
    requests = [(p, SamplingParams(max_tokens=12)) for p in PROMPTS]
    got, stats = _scripted(cfg, requests)
    want, _ = _scripted(cfg, requests, on_host=True)
    assert got == want and all(len(t) == 12 for t in got)
    assert stats["sampled_on_device"] == stats["tokens_out"] == 36
    assert stats["sampled_on_host"] == 0 and stats["logits_host_bytes"] == 0


@pytest.mark.parametrize("model", FAMILIES)
def test_mixed_batch_samples_its_seeded_row_from_pulled_logits(model):
    """One greedy row and one seeded temperature / top-k row in the same
    steps: the sampled row gets what ``ModelRunner.sample`` gives on the
    default call's row, and only its logits cross."""
    cfg = tiny_cfg(model=model)
    requests = [(PROMPTS[0], SamplingParams(max_tokens=10)),
                (PROMPTS[1], SAMPLED)]
    got, stats = _scripted(cfg, requests)
    want, _ = _scripted(cfg, requests, on_host=True)
    assert got == want and [len(t) for t in got] == [10, 10]
    greedy_alone, _ = _scripted(cfg, [(PROMPTS[1],
                                       SamplingParams(max_tokens=10))])
    assert got[1] != greedy_alone[0]            # it did sample
    vocab = resolve_model(cfg)[1].vocab_size
    assert stats["sampled_on_device"] == 10
    assert stats["sampled_on_host"] == 10
    assert stats["logits_host_bytes"] == 10 * vocab * 4


@pytest.mark.parametrize("model", FAMILIES)
def test_greedy_run_through_the_loop_thread_pulls_no_logits(model):
    eng = LLMEngine(tiny_cfg(model=model))
    try:
        streams = [eng.submit(p, SamplingParams(max_tokens=8))
                   for p in PROMPTS]
        assert all(len(s.tokens()) == 8 for s in streams)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert stats["logits_host_bytes"] == 0 and stats["sampled_on_host"] == 0
    assert stats["sampled_on_device"] == stats["tokens_out"] == 24


def test_prefill_remote_takes_its_first_token_by_the_same_rule():
    """An exported prefill's first token: the device's id for a greedy
    request, ``sample`` on the pulled row for a seeded one."""
    from ray_tpu.serve.llm.model_runner import ModelRunner
    eng = LLMEngine(tiny_cfg(), start=False)
    try:
        logits, _, _ = eng.runner.prefill(PROMPTS[0])
        assert eng.stats()["sampled_on_device"] == 0    # not the engine's
        greedy = eng.prefill_remote(PROMPTS[0], SamplingParams())
        assert greedy["first_token"] == int(np.argmax(logits))
        stats = eng.stats()
        assert (stats["sampled_on_device"], stats["sampled_on_host"],
                stats["logits_host_bytes"]) == (1, 0, 0)
        sampled = eng.prefill_remote(PROMPTS[0], SAMPLED)
        assert sampled["first_token"] == ModelRunner.sample(logits, SAMPLED, 0)
        stats = eng.stats()
        assert (stats["sampled_on_device"], stats["sampled_on_host"],
                stats["logits_host_bytes"]) == (1, 1, logits.nbytes)
    finally:
        eng.shutdown()


# --------------- the loop keeps one decode step in flight (ISSUE 37)
class _Solo:
    """The oracle: one request alone through the runner's default calls,
    in the order the loop had before it kept a step in flight (each step
    pulled before the next is built, its token chosen here from the
    pulled logits and handed back as ``tokens``), on an engine of its
    own whose pool is never short."""

    def __init__(self, cfg, params=None):
        import dataclasses
        self.eng = LLMEngine(dataclasses.replace(cfg, num_blocks=64),
                             params=params, start=False)

    def tokens(self, prompt, sp):
        from ray_tpu.serve.llm.model_runner import ModelRunner
        runner, cache = self.eng.runner, self.eng.cache
        cache.alloc_seq("solo", len(prompt))
        try:
            logits, ks, vs = runner.prefill(prompt)
            cache.scatter_prefill("solo", ks, vs, len(prompt))
            out = [ModelRunner.sample(logits, sp, 0)]
            tables = np.zeros((1, self.eng.cfg.max_blocks_per_seq), np.int32)
            while len(out) < sp.max_tokens and out[-1] != sp.stop_token:
                cache.append_slot("solo")
                table = cache.table("solo")
                tables[0, :len(table)] = table
                at = np.asarray([len(prompt) + len(out) - 1], np.int32)
                logits, _, _ = runner.decode(
                    np.asarray([out[-1]], np.int32), at, cache.pool, tables,
                    at)
                out.append(ModelRunner.sample(logits[0], sp, len(out)))
            return out
        finally:
            cache.free_seq("solo")


def _telling_weights(monkeypatch, cfg):
    """Weights under which a wrong token, position or row shows in the
    tokens (None: the engine's own).  GPT-2's tiny preset repeats its
    prompt's last token for ever (tied embeddings at random weights), so
    its position embedding is made ten times louder: the next token then
    follows from where the sequence is.  Every family's runs in float32: a
    batch of 4 and a batch of 1 are different programs, and the oracle
    compares tokens, not logits.  (Until PR 69 Falcon-H1's alone did.  In
    bf16 a sampled row's fifth and sixth logits lie 0.001 apart at two of
    GPT-2's ten steps, a quarter of a bf16 step at their size: where the
    two programs round one product differently the top-k's set changes
    and with it the token, which is how ``test_a_sampled_row_keeps_the_
    loop_in_step[gpt2:tiny]`` passed in one run of the whole suite and
    failed in the next.)"""
    import dataclasses

    import jax
    import jax.numpy as jnp
    mod, mcfg = resolve_model(cfg)
    wide = dataclasses.replace(mcfg, dtype=jnp.float32)
    monkeypatch.setitem(mod.PRESETS, "tiny", lambda: wide)
    mcfg = wide
    if not cfg.model.startswith("gpt2"):
        return None
    params = mod.init_params(jax.random.key(cfg.seed), mcfg)
    return {**params, "wpe": params["wpe"] * 10}


def _drive(eng, requests, late=(), cancel=None):
    """``requests`` submitted together and stepped by hand to the end;
    ``late``: (iteration, prompt, sampling) submitted after that
    iteration; ``cancel``: (iteration, index of the stream).  Returns the
    streams and, per iteration, each live sequence's block table and row
    of recurrent state."""
    streams = [eng.submit(p, sp) for p, sp in requests]
    held, it = [], 0
    while eng.step():
        it += 1
        cache = eng.cache
        held.append({sid: (cache.table(sid), cache.state_row(sid)
                           if cache.state_rows else None)
                     for sid in cache.seq_ids()})
        for at, prompt, sp in late:
            if at == it:
                streams.append(eng.submit(prompt, sp))
        if cancel is not None and cancel[0] == it:
            streams[cancel[1]].cancel()
    return streams, held


SMALL_POOL = dict(num_blocks=6, block_size=4, max_model_len=32,
                  max_prefill_tokens=16, prefill_len_buckets=(16, 32))
TWO_SLOTS = dict(max_num_seqs=2, decode_batch_buckets=(1, 2))


@pytest.mark.parametrize("traffic", ["lengths", "join", "stop", "cancel",
                                     "preempt"])
@pytest.mark.parametrize("model", FAMILIES)
def test_a_step_in_flight_changes_no_token(model, traffic, monkeypatch):
    """The loop enqueues step n+1 before it reads step n: every request
    gets its solo run's tokens, whatever ends, joins, stops, is cancelled
    or preempted on the way; the step in flight is drained where it has
    to be, by the cause counted; and blocks and state rows that a commit freed serve
    the next prefill while the discarded row of the step in flight was
    still to write them."""
    cfg = tiny_cfg(model=model, **{"preempt": SMALL_POOL,
                                   "stop": TWO_SLOTS}.get(traffic, {}))
    params = _telling_weights(monkeypatch, cfg)
    oracle = _Solo(cfg, params)
    eng = LLMEngine(cfg, params=params, start=False)
    try:
        greedy = lambda n, **kw: SamplingParams(max_tokens=n, **kw)  # noqa: E731
        late, cancel = (), None
        if traffic == "lengths":
            requests = [(PROMPTS[0], greedy(3)), (PROMPTS[1], greedy(7)),
                        (PROMPTS[2], greedy(12))]
        elif traffic == "join":
            requests = [(PROMPTS[0], greedy(10)), (PROMPTS[1], greedy(12))]
            late = [(5, PROMPTS[2], greedy(6))]
        elif traffic == "stop":
            # the last token of the solo run that no earlier one equals,
            # a decode step's: the stop hits at that step's commit, with
            # the next step, and the sequence's row in it, enqueued
            solo = oracle.tokens(PROMPTS[0], greedy(20))
            k = max(i for i in range(1, 19) if solo[i] not in solo[:i])
            requests = [(PROMPTS[0], greedy(20, stop_token=solo[k])),
                        (PROMPTS[1], greedy(30)), (PROMPTS[2], greedy(6))]
        elif traffic == "cancel":
            requests = [(PROMPTS[0], greedy(10)), (PROMPTS[1], greedy(30)),
                        (PROMPTS[2], greedy(12))]
            cancel = (7, 1)
        else:
            requests = [([1 + i, 2, 3], greedy(12)) for i in range(3)]
        streams, held = _drive(eng, requests, late, cancel)
        stats = eng.stats()
        want = [oracle.tokens(p, sp) for p, sp in list(requests)
                + [(p, sp) for _, p, sp in late]]
        for i, (stream, tokens) in enumerate(zip(streams, want)):
            if cancel is not None and i == cancel[1]:
                got, done = stream.poll(max_items=64, timeout=0)
                assert not done and 0 < len(got) < len(tokens)
                assert got == tokens[:len(got)]
            else:
                assert stream.tokens() == tokens, i
        # it engaged, every step was read once, and nothing is held
        drains = stats["decode_drains"]
        assert stats["decode_steps_ahead"] > 0
        assert stats["decode_steps"] == \
            stats["decode_steps_ahead"] + sum(drains.values())
        assert stats["running"] == stats["waiting"] == 0
        assert eng.cache.used_block_count() == 0
        assert stats["state_rows_used"] == 0
        assert stats["sampled_on_device"] == stats["tokens_out"]
        assert stats["decode_rows_discarded"] == (traffic == "stop")
        assert drains["sampled"] == 0
        assert (stats["preemptions"] > 0) == (traffic == "preempt")
        if traffic == "lengths":
            # no row-step for a sequence known to end by length, and the
            # one drain is the last step's
            assert drains == dict(sampled=0, pressure=0, admit=0, tail=1)
            assert stats["decode_steps"] == 11
        elif traffic in ("join", "cancel"):
            assert drains["admit"] >= 1 and drains["pressure"] == 0
        elif traffic == "preempt":
            assert drains["pressure"] >= 1
        else:
            assert stream_reason(streams[0]) == "stop"
            assert want[0][-1] == requests[0][1].stop_token
            assert len(want[0]) == k + 1 < 20 and drains["admit"] >= 1
            # the third request waited for a slot: it was given the
            # stopped sequence's blocks and row, which the step in flight
            # at the stop still wrote, and reads what its own prefill and
            # steps wrote (its tokens are its solo run's, above)
            stopped, third = streams[0].seq_id, streams[2].seq_id
            last = [h[stopped] for h in held if stopped in h][-1]
            first = next(h[third] for h in held if third in h)
            assert set(last[0]) & set(first[0]) and first[1] == last[1]
    finally:
        eng.shutdown()
        oracle.eng.shutdown()


def stream_reason(stream):
    stream.poll(timeout=0)
    return stream.finish_reason


@pytest.mark.parametrize("model", FAMILIES)
def test_a_sampled_row_keeps_the_loop_in_step(model, monkeypatch):
    """A seeded temperature / top-k row beside a greedy one: its token is
    drawn on the host from the step's pulled logits, so while it runs
    every step is read before the next is built (no step is enqueued
    behind another) and it gets its seed's tokens; when it has ended, the
    greedy row goes on with a step in flight."""
    cfg = tiny_cfg(model=model)
    params = _telling_weights(monkeypatch, cfg)
    oracle = _Solo(cfg, params)
    eng = LLMEngine(cfg, params=params, start=False)
    try:
        requests = [(PROMPTS[0], SamplingParams(max_tokens=24)),
                    (PROMPTS[1], SAMPLED)]
        streams = [eng.submit(p, sp) for p, sp in requests]
        for _ in range(2 + SAMPLED.max_tokens - 1):   # two prefills first
            assert eng.step()
            assert eng.stats()["decode_steps_ahead"] == 0
        mid = eng.stats()
        assert mid["decode_drains"]["sampled"] == mid["decode_steps"] == \
            SAMPLED.max_tokens - 1
        assert [s.sampling.greedy for s in eng.sched.running] == [True]
        while eng.step():
            pass
        stats = eng.stats()
        assert [s.tokens() for s in streams] == \
            [oracle.tokens(p, sp) for p, sp in requests]
        assert stats["decode_steps_ahead"] == 24 - SAMPLED.max_tokens - 1
        assert stats["decode_drains"] == dict(
            sampled=SAMPLED.max_tokens - 1, pressure=0, admit=0, tail=1)
        assert stats["sampled_on_host"] == SAMPLED.max_tokens
    finally:
        eng.shutdown()
        oracle.eng.shutdown()


@pytest.mark.parametrize("placed", ["by_default", "committed"])
@pytest.mark.parametrize("model", FAMILIES)
def test_a_step_behind_another_runs_the_warmed_executable(model, placed):
    """The harness warms each bucket through the default call alone, with
    numpy arguments: the first step enqueued behind another (the last
    step's ids a device array, a real row map) builds nothing and adds no
    entry to the jitted step's cache, whether the weights were placed by
    default (the harness's) or committed to a device."""
    import jax
    cfg = tiny_cfg(model=model)
    mod, mcfg = resolve_model(cfg)
    params = mod.init_params(jax.random.key(cfg.seed), mcfg)
    if placed == "committed":
        params = jax.device_put(params, jax.devices()[0])
    eng = LLMEngine(cfg, params=params, start=False)
    try:
        runner, cache = eng.runner, eng.cache
        maxb = cfg.max_blocks_per_seq
        for prompt in PROMPTS:
            runner.prefill([0] * len(prompt))
        for b in cfg.decode_batch_buckets:            # _warm_programs
            runner.decode(np.zeros(b, np.int32), np.zeros(b, np.int32),
                          cache.pool, np.zeros((b, maxb), np.int32),
                          np.ones(b, np.int32))
        built = (runner.compiles, runner._decode._cache_size())
        streams = [eng.submit(p, SamplingParams(max_tokens=n))
                   for p, n in zip(PROMPTS, (9, 4, 6))]
        while eng.step():
            pass
        assert [len(s.tokens()) for s in streams] == [9, 4, 6]
        stats = eng.stats()
        assert stats["decode_steps_ahead"] > 0
        assert stats["span_s"]["llm.compile"][0] == stats["compiles"]
        assert (runner.compiles, runner._decode._cache_size()) == built
    finally:
        eng.shutdown()


def test_weights_shared_through_shm_plane():
    from ray_tpu.serve.llm import weights as wmod
    key = f"testshare_{os.getpid()}"
    calls = [0]

    def init_fn():
        import jax
        from ray_tpu.models import gpt2
        # stamp the call ordinal into the weights: an attach returns the
        # PUBLISHED bytes (stamp 1) while a silent re-init would carry a
        # later stamp.  (eval_shape re-traces this body abstractly on
        # attach, so a call counter alone cannot distinguish the paths.)
        calls[0] += 1
        params = gpt2.init_params(jax.random.key(0), gpt2.tiny())
        stamp = float(calls[0])
        return jax.tree_util.tree_map(lambda x: x + stamp, params)

    try:
        a = wmod.publish_or_attach(key, init_fn)
        b = wmod.publish_or_attach(key, init_fn)
        base = wmod._seg_path(key, os.getpid())
        assert os.path.exists(base)             # segment published
        np.testing.assert_array_equal(np.asarray(a["wte"]),
                                      np.asarray(b["wte"]))
        # release() is the graceful-shutdown path; the pid-embedded name
        # makes a SIGKILLed publisher's segment reapable instead
        wmod.release(key)
        assert not os.path.exists(base)
        assert wmod._live_segment(key) is None
    finally:
        wmod.release(key)
        try:
            os.unlink(wmod._lock_path(key))
        except OSError:
            pass


# ---------------------------------------------------- serve integration
def test_serve_llm_streaming_and_stats(ray_start_regular):
    from ray_tpu import serve
    app = llm_deployment(tiny_cfg(share_weights=True)).bind()
    h = serve.run(app, name="llm", route_prefix="/llm",
                  _wait_timeout_s=240 * time_scale())
    req = {"prompt": [4, 8, 15], "max_tokens": 6}
    toks = [int(x.strip()) for x in h.remote(req).result()]
    assert len(toks) == 6
    rs = [h.remote(req) for _ in range(4)]
    outs = [[int(x.strip()) for x in r.result()] for r in rs]
    assert all(o == toks for o in outs)
    st = h.engine_stats.remote().result()
    assert st["prefill_steps"] >= 5 and st["tokens_out"] >= 30
    # HTTP chunked path through the proxy
    import json
    import urllib.request
    addr = serve.get_http_address()
    r = urllib.request.urlopen(urllib.request.Request(
        f"http://{addr[0]}:{addr[1]}/llm",
        data=json.dumps(req).encode(), method="POST"), timeout=120)
    assert [int(x) for x in r.read().decode().split()] == toks
    serve.shutdown()


def test_serve_llm_multiplexed_models(ray_start_regular):
    """Model selection rides @serve.multiplexed + router affinity: one
    deployment serves two model families, picked per request."""
    from ray_tpu import serve
    app = llm_deployment(tiny_cfg()).bind()
    h = serve.run(app, name="llmx", route_prefix="/llmx",
                  _wait_timeout_s=240 * time_scale())
    req = {"prompt": [3, 5, 7], "max_tokens": 5}
    base = [int(x.strip()) for x in h.remote(req).result()]
    other = [int(x.strip()) for x in h.options(
        multiplexed_model_id="llama:tiny").remote(req).result()]
    assert len(base) == len(other) == 5
    assert base != other        # different family actually served
    serve.shutdown()


def test_naive_baseline_serves(ray_start_regular):
    from ray_tpu import serve
    app = naive_llm_deployment(tiny_cfg()).bind()
    h = serve.run(app, name="llmnaive", route_prefix="/llmnaive",
                  _wait_timeout_s=240 * time_scale())
    req = {"prompt": [4, 8, 15], "max_tokens": 6}
    toks = [int(x.strip()) for x in h.remote(req).result()]
    assert len(toks) == 6
    serve.shutdown()


# ------------------------------------------------------------ chaos case
def test_chaos_sigkill_decode_replica_no_leaked_kv(monkeypatch):
    """SIGKILL a decode replica mid-generation under the resource
    sanitizer: in-flight streams fail cleanly (RayServeError, not a
    hang), the controller replaces the replica and new traffic flows.
    The KV pool is device memory: it went with the killed process, and
    there is no segment that could leak (ISSUE 6 satellite)."""
    import signal

    from ray_tpu import serve
    monkeypatch.setenv("RAY_TPU_RESOURCE_SANITIZER", "1")
    ray_tpu.init(num_cpus=4)
    try:
        app = llm_deployment(tiny_cfg()).bind()
        h = serve.run(app, name="llmchaos", route_prefix="/llmchaos",
                      _wait_timeout_s=300 * time_scale())
        warm = h.remote({"prompt": [1, 2], "max_tokens": 2}).result()
        assert len(list(warm)) == 2
        st = h.engine_stats.remote().result()
        victim_pid = st["pid"]
        # long generation, token-granular stream; kill mid-flight
        gen = h.remote({"prompt": [3, 4, 5], "max_tokens": 48}).result()
        got = [next(gen), next(gen)]
        assert len(got) == 2
        os.kill(victim_pid, signal.SIGKILL)
        with pytest.raises(ray_tpu.exceptions.RayServeError):
            for _ in gen:       # fails cleanly, never hangs
                pass
        # controller replaces the replica; a NEW request succeeds
        deadline = time.monotonic() + 240 * time_scale()
        out = None
        while time.monotonic() < deadline:
            try:
                out = [int(x.strip()) for x in h.remote(
                    {"prompt": [1, 2], "max_tokens": 3}).result(
                        timeout_s=30)]
                if len(out) == 3:
                    break
            except Exception:  # noqa: BLE001 - replica still restarting
                time.sleep(0.5)
        assert out is not None and len(out) == 3, out
        st2 = h.engine_stats.remote().result()
        assert st2["pid"] != victim_pid
        serve.shutdown()
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()
