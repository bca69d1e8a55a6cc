"""Keye-VL-2.0's stack under its learned index (``llama:tiny-keye``: an
indexer of 4 heads x 8 in every layer, 12 positions chosen, far under the
contexts here) against its plain reference, ``perfbench/reference/
keye_ref.py``: logits and not tokens, and the chosen positions themselves.

Tolerances.  The tiny preset computes in float32, as the reference does, so
equal mathematics agrees to rounding: 2e-4 on logits of spread 1 (the
program's products run at the backend's default precision, the reference's
at "highest").  A wrong selection moves logits by their own spread at a
top-k of 12 (the dense path differs by 5: ``test_a_selection_matters``), so
every fault is four orders over the tolerance.  Chosen SETS are compared
exactly, position by position: program and reference both score in float32
here, and a seed whose scores at a cut lay within rounding of each other
would show as a failure to look at, not pass unseen.
"""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import keye_ref as ref
from ray_tpu.models import llama
from ray_tpu.ops import indexed_attention as ix
from ray_tpu.ops.paged_attention import indexed_attention_decode
from ray_tpu.serve import llm
from ray_tpu.serve.llm import kv_cache
from ray_tpu.serve.llm.kv_cache import PagedKVCache

CFG = llama.tiny_keye()
SETTINGS = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts_per_tok=2, rms_norm_eps=1e-6, rope_theta=1e7,
                sa_config=dict(indexer_head_dim=8, indexer_num_heads=4,
                               indexer_num_kv_heads=1, topk=12))
ATOL = 2e-4
BS, C, TOPK = 8, CFG.prefill_chunk, CFG.index_topk


def _check_module():
    spec = importlib.util.spec_from_file_location(
        "keye_check", Path(__file__).parent.parent / "benchmarks"
        / "keye_check.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("keye_check", mod)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(3), CFG)


def _engine(params=None, **over):
    cfg = llm.EngineConfig(**{**dict(
        model="llama:tiny-keye", block_size=BS, num_blocks=64,
        max_num_seqs=4, max_prefill_tokens=128, max_model_len=128,
        decode_batch_buckets=(4,), prefill_len_buckets=(32, 64, 96, 128),
        share_weights=False, seed=3), **over})
    return llm.LLMEngine(cfg, params=params, start=False)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 200, n)]


def _run_out(eng, limit=4000):
    for _ in range(limit):
        if not eng.step() and not eng.sched.has_work():
            return
    raise AssertionError("the engine did not finish")


def _stepped(eng, prompt, steps):
    """A prompt through the runner's chunks and the paged cache, then
    ``steps`` greedy decode steps: every pass's logits and the experts
    chosen at every position."""
    runner, cache = eng.runner, eng.cache
    n = len(prompt)
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
        assert ks.shape == (2, 4, CFG.n_kv_head + 1, CFG.head_dim)
        assert vs.shape == (2, 4, CFG.n_kv_head, CFG.head_dim)
        chose.append(np.asarray(runner.choices)[:, :1])
        # as the serving job does: rewriting what the step wrote changes
        # nothing, the index key included
        cache.write_token(blk, off, np.asarray(ks[:, 0], np.float32),
                          np.asarray(vs[:, 0], np.float32))
        got.append(lg[0])
    return seq, got, np.concatenate(chose, axis=1)


# ------------------------------------------------------ program vs reference
@pytest.mark.parametrize("n", [9, 40, 70])
def test_forward_is_the_references(params, n):
    """The whole forward (one run from an empty staging) against the
    reference, which chooses its own positions."""
    toks = np.asarray([_prompt(n, seed=n)])
    want = ref.logits(params, toks, SETTINGS)
    got = np.asarray(llama.forward(params, jnp.asarray(toks), CFG))
    assert np.abs(got - want).max() < ATOL


def test_a_selection_matters(params):
    """At a top-k of 12 the dense path is another model: what the other
    tests' tolerance is set against."""
    toks = np.asarray([_prompt(70, seed=70)])
    want = ref.logits(params, toks, SETTINGS)
    dense = ref.logits(params, toks, SETTINGS, select="dense")
    assert np.abs(dense - want).max() > 1.0


def test_up_to_topk_positions_the_logits_are_the_dense_paths(params):
    """While t + 1 <= topk every position is chosen: the program's logits
    equal the dense path's, and one position past it they do not."""
    toks = np.asarray([_prompt(TOPK + 6, seed=1)])
    got = np.asarray(llama.forward(params, jnp.asarray(toks), CFG))[0]
    dense = ref.logits(params, toks, SETTINGS, select="dense")[0]
    assert np.abs(got[:TOPK] - dense[:TOPK]).max() < ATOL
    assert np.abs(got[TOPK:] - dense[TOPK:]).max() > 100 * ATOL


@pytest.mark.parametrize("n,steps", [(61, 9), (30, 8), (7, 8)])
def test_prefill_in_chunks_then_paged_decode_is_the_references(params, n,
                                                               steps):
    """A prompt through the chunked prefill (index keys staged beside
    K/V), its rows scattered into the K/V pool and the index plane, then
    decode steps through both: every pass's logits against ONE reference
    forward over the final sequence under the program's choice of experts
    (the reference chooses its own positions).  61 + 9 crosses a chunk's
    edge (32) in prefill and a page's edge (64) in decode; 7 + 8 crosses
    topk (12) in decode."""
    eng = _engine(params)
    try:
        seq, got, chose = _stepped(eng, _prompt(n, seed=n), steps)
        want, audit = ref.logits(params, [seq], SETTINGS, choices=chose)
        diffs = [float(np.abs(g - want[0, n - 1 + i]).max())
                 for i, g in enumerate(got)]
        assert max(diffs) < ATOL, diffs
        assert audit["worst_margin"] < 1e-5
        eng.cache.free_seq("s")
        assert eng.cache.free_block_count() == eng.cache.num_blocks
    finally:
        eng.shutdown()


# ------------------------------------------------------- the chosen positions
class _Recorded:
    """The program's two cuts, recorded as they run."""

    def __init__(self, monkeypatch):
        self.masks, self.lists = [], []
        mask, listed = ix.topk_mask, ix.top_positions

        def topk_mask(scores, k):
            out = mask(scores, k)
            jax.debug.callback(lambda m: self.masks.append(np.asarray(m)),
                               out, ordered=True)
            return out

        def top_positions(scores, ctx_lens, k, names=None):
            front, count = listed(scores, ctx_lens, k, names)

            def keep(front, count, ctx, *names):
                # the program's lists hold pool rows: back to positions,
                # the new token's own (the last name, -1) named as ctx
                ids = np.asarray(front)
                if names:
                    # (a table's columns past the context name block 0)
                    last = names[0].shape[1] - 1
                    where = [{**{int(n): p for p, n in
                                 enumerate(row[:int(c)])}, -1: last}
                             for row, c in zip(np.asarray(names[0]),
                                               np.asarray(ctx))]
                    ids = np.asarray([[where[b][int(n)] for n in
                                       ids[b, :int(count[b])]]
                                      + [0] * (ids.shape[1] - int(count[b]))
                                      for b in range(len(ids))])
                    ids = np.where(ids == last, np.asarray(ctx)[:, None], ids)
                self.lists.append((ids, np.asarray(count)))

            jax.debug.callback(keep, front, count, ctx_lens,
                               *([] if names is None else [names]),
                               ordered=True)
            return front, count

        monkeypatch.setattr(ix, "topk_mask", topk_mask)
        monkeypatch.setattr(ix, "top_positions", top_positions)


def _reference_sets(params, seq):
    """The positions the reference chooses at every layer and query of a
    sequence, (layers, T, T) bool."""
    *_, sets = ref.hidden(params, np.asarray([seq]), SETTINGS, chosen=True)
    return sets[:, 0]


def test_the_program_chooses_the_references_positions(params, monkeypatch):
    """Position by position, in chunked prefill, whole prefill and decode:
    the sets the program's two cuts return are the reference's, across a
    chunk's edge, a page's edge and topk itself."""
    rec = _Recorded(monkeypatch)
    n, steps = 61, 9
    eng = _engine(params)
    try:
        seq, _, _ = _stepped(eng, _prompt(n, seed=n), steps)
        jax.effects_barrier()
    finally:
        eng.shutdown()
    want = _reference_sets(params, seq)
    assert want.sum(-1).max() == TOPK
    # chunked prefill: two chunks of 32 queries a layer against the 64
    # staged positions, layer-major inside a chunk
    chunks = [rec.masks[i:i + 2] for i in range(0, 4, 2)]
    for c, per_layer in enumerate(chunks):
        for layer, mask in enumerate(per_layer):
            rows = range(c * C, min((c + 1) * C, n))
            for t in rows:
                assert (mask[t - c * C, :n] == want[layer, t, :n]).all(), \
                    (c, layer, t)
                assert not mask[t - c * C, t + 1:].any()
    # decode: a list a layer a step, the new token's own named as ctx
    lists = rec.lists[-2 * steps:]
    for i in range(steps):
        t = n + i
        for layer in range(2):
            ids, count = lists[2 * i + layer]
            assert count[0] == min(t + 1, TOPK)
            assert sorted(ids[0, :count[0]]) == \
                list(np.flatnonzero(want[layer, t, :t + 1])), (i, layer)
    # whole prefill of the final sequence: one run, the same sets
    rec.masks.clear()
    llama.forward(params, jnp.asarray([seq]), CFG)
    jax.effects_barrier()
    for layer, mask in enumerate(rec.masks[-2:]):
        assert (mask[:len(seq), :len(seq)] == want[layer]).all()


def test_equal_scores_go_to_the_lower_position():
    """Every cut takes the lower position of equal scores, a zero of either
    sign included: the mask of a run, the list of a decode row, the
    reference's."""
    inf = -np.inf
    scores = np.asarray([[1.0, 0.0, 2.0, -0.0, 0.0, 2.0, inf, inf],
                         [0.5, 0.5, 0.5, 0.5, 0.5, inf, inf, inf],
                         [3.0, inf, inf, inf, inf, inf, inf, inf]],
                        np.float32)
    want = np.asarray([[1, 1, 1, 0, 0, 1, 0, 0],
                       [1, 1, 1, 1, 0, 0, 0, 0],
                       [1, 0, 0, 0, 0, 0, 0, 0]], bool)
    clean = np.where(scores == 0, 0.0, scores)
    assert (np.asarray(ix.topk_mask(jnp.asarray(clean), 4)) == want).all()
    t = np.asarray([5, 4, 0])
    seen = np.where(np.isfinite(scores), scores, 0.0)
    assert (np.asarray(ref.chosen_positions(jnp.asarray(seen), t, 4))
            == want).all()
    # a decode row: the own score stands last and is the highest position
    row = jnp.asarray([[2.0, 2.0, inf, inf, 2.0]])
    ids, count = ix.top_positions(row, jnp.asarray([2]), 2)
    assert sorted(np.asarray(ids[0])) == [0, 1] and int(count[0]) == 2
    ids, count = ix.top_positions(row, jnp.asarray([2]), 3)
    assert sorted(np.asarray(ids[0])) == [0, 1, 2] and int(count[0]) == 3
    # listed by pool row instead: the chosen positions' own, rising
    tables = jnp.asarray([[5, 3]])
    rows = ix.pool_rows(tables, 2)
    assert rows.tolist() == [[10, 11, 6, 7, -1]]
    listed, count = ix.top_positions(row, jnp.asarray([2]), 3, rows)
    assert listed.tolist() == [[-1, 10, 11]] and int(count[0]) == 3
    # fewer candidates than k: the list's tail is zeros and not counted
    listed, count = ix.top_positions(row, jnp.asarray([2]), 4, rows)
    assert listed.tolist() == [[-1, 10, 11, 0]] and int(count[0]) == 3


@pytest.mark.parametrize("k", [1, 5, 64, 200])
def test_the_cut_by_counting_is_a_sorts(k):
    """``topk_mask`` (no sort: the k-th largest by its bits) against a
    stable sort, on scores with many ties, negatives, zeros and rows with
    fewer candidates than k."""
    rng = np.random.default_rng(k)
    scores = rng.integers(-6, 6, (40, 96)).astype(np.float32) / 4
    scores[rng.random((40, 96)) < 0.3] = -np.inf
    scores[7] = -np.inf
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, order, True, axis=1)
    want &= np.isfinite(scores)
    got = np.asarray(jax.jit(lambda s: ix.topk_mask(s, k))(scores))
    assert (got == want).all()


def test_the_score_kernel_is_the_tiles():
    """The Pallas score pass (interpret mode) against plain jax.numpy, at a
    run that starts inside the staging."""
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (256, 4, 128))
    w = jax.random.normal(ks[1], (256, 4))
    keys = jax.random.normal(ks[2], (1024, 128))
    first = 512
    got = ix._scores_kernel(q, w, keys, first, interpret=True)
    want = ix._scores_tiles(q, w, keys, first + jnp.arange(256))
    fin = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == fin).all()
    assert np.abs(np.asarray(got)[fin] - np.asarray(want)[fin]).max() < 1e-3


def test_the_walk_over_positions_reads_the_listed_rows():
    """``indexed_attention_decode`` against attention written out over the
    listed positions, the new token's own among them or not."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=kv_cache.device_shape(6, 3, BS, 2, 16)
                                  ), jnp.float32)
    tables = jnp.asarray([[4, 1, 5], [2, 0, 3]], jnp.int32)
    ctx = jnp.asarray([19, 9], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(2, 2, 16)), jnp.float32)
                    for _ in range(2))
    positions = jnp.asarray([[3, 19, 17, 8, 0], [9, 2, 7, 0, 0]], jnp.int32)
    count = jnp.asarray([5, 3], jnp.int32)
    got = np.asarray(indexed_attention_decode(
        q, pool, 1, tables, ctx, k_new, v_new, positions, count))
    rows = jnp.where(positions == ctx[:, None], -1, jnp.take_along_axis(
        tables, positions // BS, axis=1) * BS + positions % BS)
    assert (np.asarray(indexed_attention_decode(
        q, pool, 1, tables, ctx, k_new, v_new, positions, count, rows))
        == got).all()
    for b in range(2):
        ks, vs = [], []
        for p in np.asarray(positions[b, :int(count[b])]):
            if p == int(ctx[b]):
                ks.append(np.asarray(k_new[b])), vs.append(np.asarray(v_new[b]))
                continue
            blk = int(tables[b, p // BS])
            ks.append(np.asarray(pool[1, 0, blk, p % BS, :32]).reshape(2, 16))
            vs.append(np.asarray(pool[1, 1, blk, p % BS, :32]).reshape(2, 16))
        ks, vs = np.stack(ks), np.stack(vs)                    # (K, KV, D)
        for h in range(4):
            s = ks[:, h // 2] @ np.asarray(q[b, h]) / 4.0
            p = np.exp(s - s.max())
            assert np.abs(got[b, h] - (p / p.sum()) @ vs[:, h // 2]).max() \
                < 1e-5


# ------------------------------------------------------------------ faults
@pytest.mark.parametrize("kind", ["lowest", "first", "short"])
def test_a_broken_selection_fails_on_the_cpu(params, kind):
    """Each fault ``benchmarks/keye_check.py`` injects on the chip moves the
    program's logits off the reference's by far more than the tolerance, in
    prefill and in decode (at 2,048 chosen positions and random weights the
    chip's check of logits cannot see all of them: perfbench/KEYE.md)."""
    check = _check_module()
    n, steps = 61, 4
    with check.broken(kind):
        eng = _engine(params)
        try:
            seq, got, chose = _stepped(eng, _prompt(n, seed=n), steps)
        finally:
            eng.shutdown()
    want, _ = ref.logits(params, [seq], SETTINGS, choices=chose)
    diffs = [float(np.abs(g - want[0, n - 1 + i]).max())
             for i, g in enumerate(got)]
    assert diffs[0] > 100 * ATOL and max(diffs[1:]) > 100 * ATOL, diffs
    # and the reference's own wrong selections are those faults
    toks = np.asarray([seq])
    wrong = ref.logits(params, toks, SETTINGS, select=kind)
    assert np.abs(wrong - ref.logits(params, toks, SETTINGS)).max() \
        > 100 * ATOL
    # a decode row's list is made from the same cut and is broken with it
    row = jnp.asarray([[3.0, 1.0, 2.0, 0.5, -np.inf, 4.0]])
    ids, count = ix.top_positions(row, jnp.asarray([4]), 2)
    assert set(np.asarray(ids[0, :int(count[0])]).tolist()) == {0, 4}
    with check.broken(kind):
        ids, count = ix.top_positions(row, jnp.asarray([4]), 2)
    assert set(np.asarray(ids[0, :int(count[0])]).tolist()) == {
        "lowest": {1, 3}, "first": {0, 1}, "short": {4}}[kind]


def test_the_chips_check_of_the_kernels_runs_at_a_small_size():
    """``keye_check.py --kernels`` (the chosen sets of the kernels' path
    against a host sort's on contrived whole-number keys, at the cell's size
    on the chip) at a small one: the paths here are the plain ones."""
    out = _check_module().kernels_against_a_sort(5, t_q=64, s_len=512, k=24)
    assert [v["rows_differing"] for name, v in out.items()
            if name != "device"] == [0, 0, 0, 0]
    assert out["chunk_at_448"]["distinct_scores_a_row"] > 50


# --------------------------------------------------------------- the cache
def test_the_module_declares_a_sixth_plane(params):
    kept = kv_cache.kept_by(llama, CFG)
    assert (kept.kv_layers, kept.index_layers, kept.index_dim,
            kept.index_topk) == (2, 2, 8, 12)
    assert not kept.staged and not kept.packed
    plain = kv_cache.kept_by(llama, llama.tiny_sdar())
    assert (plain.index_layers, plain.index_dim, plain.index_topk) \
        == (0, 0, 0)
    eng = _engine(params)
    try:
        assert [p.name for p in eng.cache.planes] == ["kv", "index"]
        assert eng.cache.pool.described["index"].shape == (2, 1, 64, BS, 128)
        assert eng.cache.index_bytes == 2 * 64 * BS * 128 * 4
        assert eng.runner.chunk == C
        assert set(eng.runner.staging_spec) == {"k", "v", "index"}
    finally:
        eng.shutdown()
    eng = llm.LLMEngine(llm.EngineConfig(
        model="llama:tiny-sdar", block_size=BS, num_blocks=16,
        max_num_seqs=2, max_model_len=64, max_prefill_tokens=64,
        prefill_len_buckets=(32, 64), decode_batch_buckets=(2,),
        share_weights=False), start=False)
    try:
        # a preset without an index: no plane, no chunk, no staging
        assert [p.name for p in eng.cache.planes] == ["kv"]
        assert eng.runner.chunk == 0 and eng.cache.index_bytes == 0
    finally:
        eng.shutdown()


def test_the_index_plane_is_written_once_a_position(params):
    """A prompt's scatter and the steps' writes put each position's key in
    the slot its K has, under the one table; the pages of a neighbour that
    comes and goes are its own; writing a token again changes nothing."""
    eng = _engine(params)
    runner, cache = eng.runner, eng.cache
    try:
        n, steps = 41, 5
        seq, got, _ = _stepped(eng, _prompt(n, seed=n), steps)
        table = cache.table("s")
        keys = cache.index_keys()                # (N, layers, bs, ID)
        held = keys[table].transpose(1, 0, 2, 3).reshape(2, -1, 8)
        # the reference's own index keys of the final sequence
        filled = held[:, :n + steps]
        assert np.abs(filled).min(-1).max() > 0 and \
            np.abs(filled).max() > 0.1
        assert not held[:, n + steps:].any()     # nothing past the last
        assert not np.delete(keys, table, axis=0).any()
        # a neighbour is prefilled, stepped and freed: "s" keeps its keys
        cache.alloc_seq("n", 50)
        lg, ks, vs = runner.prefill(_prompt(50, seed=5))
        cache.scatter_prefill("n", ks, vs, 50)
        other = cache.table("n")
        assert set(other).isdisjoint(table)
        cache.free_seq("n")
        assert (cache.index_keys()[table] == keys[table]).all()
        # the freed pages go to the next sequence, which overwrites them
        cache.alloc_seq("m", 20)
        lg, ks, vs = runner.prefill(_prompt(20, seed=6))
        cache.scatter_prefill("m", ks, vs, 20)
        assert (cache.index_keys()[table] == keys[table]).all()
    finally:
        eng.shutdown()


def test_the_engines_loop_serves_the_family(params):
    """Through submit and the loop: prompts of one chunk and of several in
    one queue, chunks between decode steps; each request's tokens are the
    greedy continuation the reference gives it ALONE, and the counts of
    positions are the steps' own."""
    eng = _engine(params)
    try:
        prompts = [_prompt(n, seed=n) for n in (20, 70, 100, 45)]
        streams = [eng.submit(p, llm.SamplingParams(max_tokens=10))
                   for p in prompts]
        _run_out(eng)
        for prompt, stream in zip(prompts, streams):
            out = stream.tokens()
            assert len(out) == 10
            want = ref.logits(params, [prompt + out[:-1]], SETTINGS)[0]
            assert out == [int(t) for t in
                           want[len(prompt) - 1:].argmax(-1)]
        stats = eng.stats()
        assert stats["preemptions"] == 0
        assert (stats["index_layers"], stats["kv_layers"]) == (2, 2)
        assert stats["prefill_chunks"] == sum(-(-len(p) // C)
                                              for p in prompts)
        # 9 decode steps a request: contexts len(p) + 1 .. len(p) + 9
        scored = sum(2 * (len(p) + 1 + i) for p in prompts for i in range(9))
        read = sum(2 * min(len(p) + 1 + i, TOPK)
                   for p in prompts for i in range(9))
        assert (stats["positions_scored"], stats["positions_read"]) \
            == (scored, read)
        assert stats["blocks_free"] == eng.cfg.num_blocks
    finally:
        eng.shutdown()


def test_a_preempted_sequence_is_prefilled_again_and_goes_on(params):
    """Under cache pressure the latest arrival is evicted: its pages (K/V
    and index keys alike) go back, it runs its chunks again, and every
    request's tokens are what they are without pressure."""
    prompts = [_prompt(n, seed=n) for n in (60, 70, 80)]

    def served(num_blocks):
        eng = _engine(params, num_blocks=num_blocks, max_num_seqs=3)
        try:
            streams = [eng.submit(p, llm.SamplingParams(max_tokens=30))
                       for p in prompts]
            _run_out(eng)
            return [s.tokens() for s in streams], eng.stats()
        finally:
            eng.shutdown()

    roomy, stats = served(64)
    assert stats["preemptions"] == 0
    tight, stats = served(34)
    assert stats["preemptions"] > 0
    assert tight == roomy
    assert stats["blocks_free"] == 34


def test_the_spans_carry_the_counts_of_positions(params, monkeypatch):
    """``llm.prefill.chunk`` and ``llm.decode.pull`` are told what their
    positions had to score and to read, from their own positions."""
    told = {}
    eng = _engine(params)
    real = kv_cache.index_reads

    def reads(contexts, topk, layers):
        out = real(contexts, topk, layers)
        told.setdefault("calls", []).append((list(map(int, contexts)), out))
        return out

    monkeypatch.setattr(kv_cache, "index_reads", reads)
    try:
        eng.runner.prefill(_prompt(40, seed=1))
    finally:
        eng.shutdown()
    (first, one), (second, two) = told["calls"]
    assert first == list(range(1, 33)) and second == list(range(33, 41))
    assert one == {"positions_scored": 2 * sum(range(1, 33)),
                   "positions_read": 2 * (sum(range(1, 13)) + 12 * 20)}
    assert two["positions_read"] == 2 * 12 * 8


@pytest.mark.parametrize("call", ["prefill_remote", "attach", "fork_seq"])
def test_what_moves_one_tables_blocks_refuses_the_family(params, call):
    eng = _engine(params)
    try:
        with pytest.raises(NotImplementedError, match="index"):
            if call == "prefill_remote":
                eng.prefill_remote(_prompt(20))
            elif call == "attach":
                eng.attach({"model": "llama:tiny-keye"})
            else:
                eng.cache.alloc_seq("s", 10)
                eng.cache.fork_seq("s", "t")
    finally:
        eng.shutdown()


def test_what_the_stack_does_not_write_is_refused():
    with pytest.raises(ValueError, match="an index needs"):
        llama.LlamaConfig(index_topk=4, index_heads=2, index_dim=8,
                          n_embd=64, n_head=4)           # no prefill_chunk
    with pytest.raises(ValueError, match="an index needs"):
        llama.LlamaConfig(index_topk=4, index_heads=2, index_dim=32,
                          n_embd=64, n_head=4, prefill_chunk=8)   # > head
    with pytest.raises(NotImplementedError, match="training under"):
        llama.loss_fn(llama.init_params(jax.random.key(0), CFG),
                      {"tokens": jnp.zeros((1, 9), jnp.int32)}, CFG)
    with pytest.raises(ValueError, match="an index plane lies under"):
        PagedKVCache(8, 2, BS, 2, 16, index_layers=1, index_dim=8)


def test_every_other_preset_draws_the_weights_it_drew():
    """The indexer's leaves are drawn from keys of their own: a preset
    without an index has the tree it had, and the Keye preset's other
    leaves are what the same stack without an index draws."""
    import dataclasses
    plain = dataclasses.replace(CFG, index_topk=0, index_heads=0,
                                index_dim=0, prefill_chunk=0)
    a = llama.init_params(jax.random.key(9), plain)
    b = llama.init_params(jax.random.key(9), CFG)
    assert "index" not in a["blocks"] and "index" in b["blocks"]
    b["blocks"].pop("index")
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
