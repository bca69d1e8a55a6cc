"""Block-sparse attention that chooses its pages (InfLLM-v2, as MiniCPM4
trains it; MiniCPM4 technical report, arXiv:2506.07900).

A query at position ``t`` sees ``n = t + 1`` positions.  Up to
``dense_len`` of them it attends to all, causally.  Past that it attends to
``topk`` *blocks* of ``block`` positions (a block is a page of the serving
cache), chosen per KV head ``g`` from compressed keys::

    Kc_j   = mean(k[stride j : stride j + kernel])     every window whole in 0..t
    p_hj   = softmax_j(q_h . Kc_j / sqrt(D))            each query head h of g
    r_j    = sum_h p_hj
    s_b    = max of r_j over the kernels that overlap block b
    chosen = block 0, every block that overlaps the last ``window``
             positions, and the best-scoring others: ``topk`` in all, ties
             towards the lower block
    o_h    = causal softmax attention of q_h over the chosen blocks' positions

``kernel = 2 stride`` (32 and 16 as published), so a kernel is two *halves*:
``Kc_j = (H_j + H_{j+1}) / kernel`` with ``H_i = sum(k[stride i : stride i +
stride])``, and a half lies in one block whole.  The halves are what the
serving cache keeps beside the K/V it pages (``serve/llm/kv_cache.py``: the
selector's cache, one slot a ``stride`` positions, paged by the sequence's
own table), and what a prefill keeps beside its staging K/V; every function
here takes halves and never pools keys over a page's edge.

Four entry points (the decode's walk over the chosen pages is the paged
kernel's, ``ops/paged_attention.py``):

* :func:`halves_of` -- keys to half-sums, the cache writers' one rule;
* :func:`choose_blocks` -- scores of kernels to the chosen blocks;
* :func:`prefill_attention` -- a run of queries over a contiguous K/V under
  each query's own choice: on a TPU a flash kernel over tiles of queries
  and keys that skips the tiles no query of its tile chose (at random
  weights neighbouring queries choose differently and few are skipped;
  trained ones choose alike), elsewhere the same tiles in ``jax.numpy``;
* :func:`decode_pages` -- one query a row against the paged halves: the
  list of table columns and the count that ``paged_attention_decode`` walks.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_HI = lax.Precision.HIGHEST
NEG_INF = jnp.finfo(jnp.float32).min


class SparseSpec(NamedTuple):
    """``sparse_config`` as the model publishes it."""

    kernel: int = 32         # kernel_size: positions a compressed key pools
    stride: int = 16         # kernel_stride
    block: int = 64          # block_size: a selection block, a cache page
    init_blocks: int = 1     # leading blocks every query reads
    window: int = 2048       # window_size: trailing positions always read
    topk: int = 64           # blocks read in all, the forced ones among them
    dense_len: int = 8192    # contexts up to this attend to everything

    def check(self) -> None:
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                f"a kernel of {self.kernel} at stride {self.stride} over "
                f"blocks of {self.block}: the selector keeps half-kernels "
                "(kernel = 2 stride) that lie whole inside a block")
        if self.dense_len < self.topk * self.block // 2:
            raise ValueError("dense_len under half the chosen positions")

    @property
    def halves_per_block(self) -> int:
        return self.block // self.stride

    def list_width(self) -> int:
        """Entries of a decode row's page list: the chosen blocks, or all
        of a context that still attends densely."""
        return max(self.topk, -(-self.dense_len // self.block))


def halves_of(k: jax.Array, n_valid, stride: int) -> jax.Array:
    """(..., T, F) keys -> (..., T / stride, F) float32: the sum of every
    ``stride`` positions, those at ``n_valid`` and past it left out (a
    half that is not full yet holds what it has)."""
    t = k.shape[-2]
    keep = (jnp.arange(t) < n_valid)[:, None]
    k = jnp.where(keep, k.astype(jnp.float32), 0.0)
    return k.reshape(*k.shape[:-2], t // stride, stride, k.shape[-1]).sum(-2)


def _kernel_logits(a: jax.Array, spec: SparseSpec, head_dim: int):
    """q . H_i for every half (..., NH) -> q . Kc_j / sqrt(D) for every
    kernel j = halves j and j + 1, (..., NH - 1)."""
    return (a[..., :-1] + a[..., 1:]) * (
        1.0 / (spec.kernel * math.sqrt(head_dim)))


def choose_blocks(logits: jax.Array, t: jax.Array, spec: SparseSpec
                  ) -> Tuple[jax.Array, jax.Array]:
    """The selection from the kernels' logits.

    logits (..., R, J): ``q_h . Kc_j / sqrt(D)`` of the R query heads of one
    KV head against every kernel slot (J = halves - 1; slots whose window
    does not lie whole in ``0..t`` hold anything); t (...,): the query's
    position.  Returns (ids (..., topk) int32: the chosen blocks, forced
    ones first by index, then by score, ties towards the lower block;
    count (...,): how many of them exist, ``min(topk, t // block + 1)``)."""
    j = logits.shape[-1]
    hb, nb = spec.halves_per_block, (j + 1) // spec.halves_per_block
    tq = t[..., None]
    # kernel j ends at stride j + kernel - 1
    whole = jnp.arange(j) * spec.stride + spec.kernel - 1 <= tq     # (.., J)
    masked = jnp.where(whole[..., None, :], logits, NEG_INF)
    p = jax.nn.softmax(masked, axis=-1)
    r = jnp.where(whole, p.sum(-2), -1.0)                           # (.., J)
    # block b meets kernels hb b - 1 .. hb b + hb - 1: those that start in
    # it, and the last that started in the block before
    r = jnp.concatenate([r, jnp.full(r.shape[:-1] + (1,), -1.0)], -1)
    starts_in = r.reshape(*r.shape[:-1], nb, hb)
    before = jnp.concatenate(
        [jnp.full(r.shape[:-1] + (1,), -1.0), starts_in[..., :-1, hb - 1]],
        -1)
    score = jnp.maximum(starts_in.max(-1), before)                  # (.., NB)
    b = jnp.arange(nb)
    last = tq // spec.block
    first_local = jnp.maximum(tq + 1 - spec.window, 0) // spec.block
    forced = (b < spec.init_blocks) | (b >= first_local)
    # a score is a sum of R probabilities: forced blocks above any, blocks
    # past the query below any
    key = jnp.where(forced, 2.0 * logits.shape[-2] + 1.0, score)
    key = jnp.where(b <= last, key, -2.0)
    # (a staging shorter than topk blocks lists what there is)
    _, ids = lax.top_k(key, min(spec.topk, nb))
    ids = jnp.pad(ids, [(0, 0)] * (ids.ndim - 1)
                  + [(0, spec.topk - ids.shape[-1])])
    return ids.astype(jnp.int32), jnp.minimum(spec.topk, t // spec.block + 1)


def _block_mask(ids: jax.Array, count: jax.Array, t: jax.Array, nb: int,
                spec: SparseSpec) -> jax.Array:
    """(..., topk) chosen ids -> (..., NB) bool; a query still under
    ``dense_len`` reads every block up to its own."""
    b = jnp.arange(nb)
    live = jnp.arange(ids.shape[-1]) < count[..., None]
    chosen = ((ids[..., None] == b) & live[..., None]).any(-2)
    dense = (t + 1 <= spec.dense_len)[..., None]
    return jnp.where(dense, b <= (t // spec.block)[..., None], chosen)


def prefill_mask(q: jax.Array, halves: jax.Array, positions: jax.Array,
                 spec: SparseSpec, query_tile: int = 256) -> jax.Array:
    """Which blocks each query of a run reads.

    q (T, KV, R, D) (normed); halves (NH, KV, D) float32 of the whole
    context so far, the run's own keys among them (slots past it hold
    anything); positions (T,).  Returns (KV, T, NB) bool."""
    t_q, kv, _, d = q.shape
    nb = halves.shape[0] // spec.halves_per_block
    tile = math.gcd(t_q, query_tile)

    # the scope is named inside the loop's body: a body's name stack is
    # relative to its loop, and a scope around the loop is lost to the
    # device trace's reader (util/tracing.op_map)
    @jax.named_scope("sparse_select")
    def one(args):
        qt, pos = args
        a = jnp.einsum("tgrd,ngd->gtrn", qt.astype(jnp.float32), halves,
                       precision=_HI)
        pos = jnp.broadcast_to(pos, (kv, tile))
        ids, count = choose_blocks(_kernel_logits(a, spec, d), pos, spec)
        return _block_mask(ids, count, pos, nb, spec)

    masks = lax.map(one, (q.reshape(t_q // tile, tile, *q.shape[1:]),
                          positions.reshape(t_q // tile, tile)))
    return jnp.moveaxis(masks, 0, 1).reshape(kv, t_q, nb)


def _key_tile(n_blocks: int, block: int, most: int = 1024) -> int:
    """Blocks a tile of keys: the largest divisor of the staging's blocks
    that keeps a tile at ``most`` positions or under."""
    return max(c for c in range(1, max(1, most // block) + 1)
               if n_blocks % c == 0)


def _masked_tiles(q, k_all, v_all, mask, positions, extent, block):
    """:func:`prefill_attention` in plain ``jax.numpy``: tiles of keys, a
    running softmax in float32, the products in ``q.dtype``.  The CPU's
    path and the kernel's reference."""
    t_q, kv, rep, d = q.shape
    nb = mask.shape[-1]
    per = _key_tile(nb, block)
    tile = per * block
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32

    def step(i, carry):
        m, l, acc = carry
        k = lax.dynamic_slice_in_dim(k_all, i * tile, tile).astype(q.dtype)
        v = lax.dynamic_slice_in_dim(v_all, i * tile, tile).astype(q.dtype)
        k = k[:, :kv * d].reshape(tile, kv, d)
        v = v[:, :kv * d].reshape(tile, kv, d)
        s = jnp.einsum("tgrd,kgd->gtrk", q, k,
                       preferred_element_type=f32) * scale
        at = i * tile + jnp.arange(tile)
        allowed = jnp.repeat(lax.dynamic_slice_in_dim(mask, i * per, per, 2),
                             block, axis=2)                   # (KV, T, tile)
        allowed &= at[None, None, :] <= positions[None, :, None]
        s = jnp.where(allowed[:, :, None, :], s, NEG_INF)
        m_next = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_next)
        # a row with nothing allowed so far carries exp(0) of masked
        # scores: the first allowed key's alpha = 0 wipes them, and every
        # query reads its own position at the latest
        p = jnp.exp(s - m_next[..., None])
        l = alpha * l + p.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "gtrk,kgd->gtrd", p.astype(q.dtype), v,
            preferred_element_type=f32)
        return m_next, l, acc

    init = (jnp.full((kv, t_q, rep), NEG_INF, f32),
            jnp.zeros((kv, t_q, rep), f32),
            jnp.zeros((kv, t_q, rep, d), f32))
    n_tiles = (jnp.asarray(extent, jnp.int32) + tile - 1) // tile
    _, l, acc = lax.fori_loop(0, n_tiles, step, init)
    return jnp.moveaxis(acc / l[..., None], 0, 1).astype(q.dtype)


# queries and keys a grid step of the kernel: 128 queries x 16 heads of a
# group are 2,048 rows against 512 keys, 4 MiB of float32 scores in VMEM
_Q_TILE, _K_TILE = 128, 512


def _flash_kernel(tiles_ref, any_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, rep):
    """One (KV head, tile of queries, tile of keys): q_ref (1, 1, rep x bq,
    D), its rows head-major (row r is query r % bq); k_ref / v_ref (bk, D)
    float32, the head's lanes of the staging; mask_ref (1, bq, bk) int8,
    causality folded in.  The tiles of keys past ``tiles_ref[0]`` and those
    in which no query of the tile may read anything (``any_ref``) do
    nothing."""
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk = mask_ref.shape[1:]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((j < tiles_ref[0]) & (any_ref[g, i, j] > 0))
    def _():
        q = q_ref[0, 0]                                     # (rep bq, D)
        k = k_ref[...].astype(q.dtype)
        v = v_ref[...].astype(q.dtype)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        allowed = mask_ref[0].astype(jnp.int32) > 0          # (bq, bk)
        s = jnp.where(allowed[None], s.reshape(rep, bq, bk), NEG_INF
                      ).reshape(rep * bq, bk)
        m = m_ref[...]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        # (a row with nothing allowed yet: see _masked_tiles)
        p = jnp.exp(s - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(q.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _flash_masked(q, k_all, v_all, mask, positions, extent, block, *,
                  interpret=False):
    """:func:`prefill_attention` as one Pallas call: a flash kernel over
    (KV head, tile of queries, tile of keys) that skips the tiles of keys
    past the run's end and those no query of its tile chose."""
    from jax.experimental.pallas import tpu as pltpu

    t_q, kv, rep, d = q.shape
    s_len = k_all.shape[0]
    bq, bk = min(_Q_TILE, t_q), min(_K_TILE, s_len)
    n_qt, n_kt = t_q // bq, s_len // bk
    # which keys each query reads, causality folded in; and whether a tile
    # of queries reads anything of a tile of keys, from the blocks alone (a
    # query's mask names no block past its own, and of its own it reads
    # itself)
    allowed = jnp.repeat(mask, block, axis=2) & (
        jnp.arange(s_len)[None, None, :] <= positions[None, :, None])
    some = mask.reshape(kv, n_qt, bq, n_kt, bk // block).any((2, 4))
    tiles = (jnp.asarray(extent, jnp.int32) + bk - 1) // bk
    # rows of a tile head-major: (KV, q tiles, rep x bq, D)
    rows = q.reshape(n_qt, bq, kv, rep, d).transpose(2, 0, 3, 1, 4) \
        .reshape(kv, n_qt, rep * bq, d)

    def keys(g, i, j, tiles_ref, any_ref):
        return (jnp.minimum(j, tiles_ref[0] - 1), g)

    def masks(g, i, j, tiles_ref, any_ref):
        return (g, i, jnp.minimum(j, tiles_ref[0] - 1))

    tile = lambda g, i, j, *prefetched: (g, i, 0, 0)           # noqa: E731
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / math.sqrt(d), rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kv, n_qt, n_kt),
            in_specs=[
                pl.BlockSpec((1, 1, rep * bq, d), tile),
                pl.BlockSpec((bk, d), keys),
                pl.BlockSpec((bk, d), keys),
                pl.BlockSpec((1, bq, bk), masks),
            ],
            out_specs=pl.BlockSpec((1, 1, rep * bq, d), tile),
            scratch_shapes=[
                pltpu.VMEM((rep * bq, 1), jnp.float32),
                pltpu.VMEM((rep * bq, 1), jnp.float32),
                pltpu.VMEM((rep * bq, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((kv, n_qt, rep * bq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="sparse_prefill",
    )(tiles.reshape(1), some.astype(jnp.int32), rows, k_all, v_all,
      allowed.astype(jnp.int8))
    return out.reshape(kv, n_qt, rep, bq, d).transpose(1, 3, 0, 2, 4) \
        .reshape(t_q, kv, rep, d)


def prefill_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                      mask: jax.Array, positions: jax.Array, extent,
                      block: int) -> jax.Array:
    """Causal softmax attention of a run of queries over a contiguous K/V,
    each query over the blocks its mask names.

    q (T, KV, R, D); k_all, v_all (S, KV * D) lane-flat float32, every
    position of the sequence so far at its own index (what lies at
    ``extent`` and past it is never read); mask (KV, T, NB), NB = S /
    block; positions (T,).  Returns (T, KV, R, D) in ``q.dtype``.

    On a TPU, at a head size of whole lanes and a run and a staging of
    whole tiles, a flash kernel (:func:`_flash_masked`); elsewhere, and the
    kernel's reference, tiles of keys in plain ``jax.numpy``."""
    t_q, kv, _, d = q.shape
    if jax.default_backend() == "tpu" and d % 128 == 0 \
            and k_all.shape[1] == kv * d and t_q % _Q_TILE == 0 \
            and k_all.shape[0] % _K_TILE == 0:
        return _flash_masked(q, k_all, v_all, mask, positions, extent, block)
    return _masked_tiles(q, k_all, v_all, mask, positions, extent, block)


def decode_pages(q: jax.Array, halves_pool: jax.Array,
                 block_tables: jax.Array, ctx_lens: jax.Array,
                 k_new: jax.Array, spec: SparseSpec
                 ) -> Tuple[jax.Array, jax.Array]:
    """The pages one decode step's rows read in one layer.

    q (B, H, D) (normed); halves_pool (N, halves a page, F): the layer's
    slab of the selector's cache; block_tables (B, MAXB); ctx_lens (B,):
    positions in the pool, so the new token stands at ``ctx_lens``; k_new
    (B, KV, D), its key, which the half it falls in does not hold yet.

    Returns (pages (B, KV, W) int32: table COLUMNS, W = spec.list_width();
    counts (B, KV) int32).  A context still under ``dense_len`` lists its
    columns in order, all of them; past it the chosen ``topk``."""
    b, h, d = q.shape
    kv = k_new.shape[1]
    rep, hb = h // kv, spec.halves_per_block
    f32 = jnp.float32
    t = ctx_lens.astype(jnp.int32)
    halves = halves_pool[block_tables]                  # (B, MAXB, hb, F)
    halves = halves.reshape(b, -1, halves.shape[-1])[..., :kv * d]
    halves = halves.reshape(b, -1, kv, d)
    # the half the new token falls in: what the pool's positions gave it
    # (nothing yet at a half's first position) and the token's own key
    own = (jnp.arange(halves.shape[1])[None, :] == (t // spec.stride)[:, None]
           )[..., None, None]
    fresh = (t % spec.stride == 0)[:, None, None, None]
    halves = jnp.where(own & fresh, 0.0, halves) \
        + jnp.where(own, k_new.astype(f32)[:, None], 0.0)
    a = jnp.einsum("bgrd,bngd->bgrn", q.astype(f32).reshape(b, kv, rep, d),
                   halves, precision=_HI)
    tg = jnp.broadcast_to(t[:, None], (b, kv))
    ids, count = choose_blocks(_kernel_logits(a, spec, d), tg, spec)
    width = spec.list_width()
    dense = (tg + 1 <= spec.dense_len)
    pages = jnp.where(dense[..., None], jnp.arange(width),
                      jnp.pad(ids, ((0, 0), (0, 0),
                                    (0, width - spec.topk))))
    held = -(-tg // spec.block)         # pages with a position in the pool
    counts = jnp.where(dense, held, count)
    return pages.astype(jnp.int32), counts.astype(jnp.int32)
