"""Attention under a learned index: each query attends to the ``topk``
positions an indexer picks for it, one by one (DeepSeek Sparse Attention's
lightning indexer, DeepSeek-V3.2-Exp's report; ``models/llama.py``,
``index_topk``).

The indexer is a small attention of its own, with projections, heads and a
cache apart from the K/V's: ``IH`` query heads of ``ID`` lanes against ONE
key head, and a weight a head::

    I_t,s = sum_j w_t,j ReLU(qI_t,j . kI_s)        s <= t
    S_t   = the topk positions s <= t of largest I_t,s: all t + 1 of them
            while t + 1 <= topk; of equal scores the lower position first
    o_t,h = softmax over s in S_t of (q_t,h . k_s / sqrt(D)) applied to v_s

The selection is exact and a position's own: no window, no sink, no page
rounding, no approximate top-k (``approx_max_k`` is another selection).
``I`` is computed in float32 from the keys as they are cached.  A score that
is zero is +0.0 wherever it is compared (``w . ReLU`` makes -0.0 under a
negative weight), so that equal scores are equal under every order used.

A run of queries (prefill, whole or in chunks):

* :func:`index_scores` -- ``I`` for a run against every staged key, ``-inf``
  where ``s > t``: on a TPU one Pallas pass over tiles that keeps the
  ``IH`` heads' products in VMEM, elsewhere the same tiles in ``jax.numpy``;
* :func:`topk_mask` -- the exact cut a row: the ``topk``-th largest score by
  a search over the bits of its float32 (32 counting passes, no sort), then
  the ties at the cut by position;
* :func:`prefill_attention` -- the flash pass over tiles that admits a pair
  ``(t, s)`` iff ``s`` is in ``S_t`` (``ops/sparse_attention``'s masked
  flash kernel, its blocks one position long).

One query a row (decode), against the paged index plane
(``serve/llm/kv_cache.py``, the ``"index"`` row):

* :func:`decode_scores` -- the score pass over the row's pages and the new
  token's own key;
* :func:`top_positions` -- the exact ``topk`` of them as a LIST: the run's
  cut (:func:`topk_mask`, a decode step's rows one tile of the kernel),
  then the chosen entries' positions, or their pool rows
  (:func:`pool_rows`), brought to the front by one sort of int32 keys;
* the walk over that list is ``ops/paged_attention.indexed_attention_decode``.
"""

from __future__ import annotations

import functools
import math
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu.ops import sparse_attention as sparse

_HI = lax.Precision.HIGHEST
NEG_INF = -jnp.inf

# queries and keys a grid step of the score kernel: a tile of float32
# scores is 0.5 MiB, the heads' products beside it as much again
_Q_TILE, _K_TILE = 256, 512


def _weighed(q, w, keys):
    """sum_j w_j ReLU(q_j . key): q (T, IH, ID), w (T, IH), keys (S, ID),
    all float32 -> (T, S) float32."""
    s = jnp.einsum("tjd,sd->tjs", q, keys, precision=_HI)
    return jnp.einsum("tjs,tj->ts", jnp.maximum(s, 0.0), w, precision=_HI)


def _plus_zero(x):
    """-0.0 -> +0.0: equal scores are then equal as bits too."""
    return jnp.where(x == 0.0, 0.0, x)


def _scores_tiles(q, w, keys, positions, tile: int = 128):
    """:func:`index_scores` in plain ``jax.numpy``, a tile of queries at a
    time: the CPU's path and the kernel's reference."""
    t_q = q.shape[0]
    tile = math.gcd(t_q, tile)
    at = jnp.arange(keys.shape[0])

    @jax.named_scope("index_score")
    def one(args):
        qt, wt, pos = args
        return jnp.where(at[None, :] <= pos[:, None],
                         _plus_zero(_weighed(qt, wt, keys)), NEG_INF)

    out = lax.map(one, (q.reshape(t_q // tile, tile, *q.shape[1:]),
                        w.reshape(t_q // tile, tile, -1),
                        positions.reshape(t_q // tile, tile)))
    return out.reshape(t_q, -1)


def _halves(x):
    """float32 -> (hi, lo) bf16 with ``hi + lo`` the value to 2^-16 of it."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _score_kernel(first_ref, q_ref, w_ref, k_ref, o_ref, *, heads):
    """One (tile of queries, tile of keys): q_ref (IH, bq, ID), w_ref (bq,
    IH), k_ref (bk, ID) float32 -> o_ref (bq, bk).  A tile of keys wholly
    past the tile's last query holds ``-inf`` and costs no product.  A
    float32 product is three bf16 passes accumulated in float32 (hi . hi +
    hi . lo + lo . hi: what ``Precision.HIGH`` is), 2^-16 of a product off
    the six-pass one at half its time."""
    i, j = pl.program_id(0), pl.program_id(1)
    bq, bk = o_ref.shape
    t_first = first_ref[0] + i * bq

    @pl.when(j * bk > t_first + bq - 1)
    def _():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    @pl.when(j * bk <= t_first + bq - 1)
    def _():
        k_hi, k_lo = _halves(k_ref[...])
        dims = (((1,), (1,)), ((), ()))
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            q_hi, q_lo = _halves(q_ref[h])
            s = sum(lax.dot_general(a, b, dims,
                                    preferred_element_type=jnp.float32)
                    for a, b in ((q_lo, k_hi), (q_hi, k_lo), (q_hi, k_hi)))
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, h:h + 1]
        t = t_first + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s_at = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        o_ref[...] = jnp.where(s_at <= t, jnp.where(acc == 0.0, 0.0, acc),
                               NEG_INF)


def _scores_kernel(q, w, keys, first, *, interpret=False):
    """:func:`index_scores` as one Pallas call; the run's positions are
    ``first .. first + T - 1``."""
    from jax.experimental.pallas import tpu as pltpu

    t_q, heads, d = q.shape
    s_len = keys.shape[0]
    bq, bk = min(_Q_TILE, t_q), min(_K_TILE, s_len)
    # a tile of keys past the tile's queries is not fetched again
    def key_tile(i, j, first_ref):
        return (jnp.minimum(j, (first_ref[0] + (i + 1) * bq - 1) // bk), 0)

    return pl.pallas_call(
        functools.partial(_score_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t_q // bq, s_len // bk),
            in_specs=[
                pl.BlockSpec((heads, bq, d), lambda i, j, f: (0, i, 0)),
                pl.BlockSpec((bq, heads), lambda i, j, f: (i, 0)),
                pl.BlockSpec((bk, d), key_tile),
            ],
            out_specs=pl.BlockSpec((bq, bk), lambda i, j, f: (i, j))),
        out_shape=jax.ShapeDtypeStruct((t_q, s_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="index_score",
    )(jnp.asarray(first, jnp.int32).reshape(1), q.transpose(1, 0, 2), w, keys)


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array,
                 first) -> jax.Array:
    """``I`` of a run of queries against every staged key.

    q (T, IH, ID) and w (T, IH): the run's index queries (rotated) and head
    weights; keys (S, F) float32: the staged index keys, every position of
    the sequence so far at its own index, a key its first ``ID`` lanes (what
    lies past the run's last position is never admitted); first: the run's
    first position (traced).  Returns (T, S) float32, ``-inf`` at ``s > t``.

    On a TPU, at whole tiles, one Pallas pass (:func:`_scores_kernel`);
    elsewhere, and the kernel's reference, tiles in ``jax.numpy``."""
    t_q, _, d = q.shape
    f32 = jnp.float32
    q, w = q.astype(f32), w.astype(f32)
    if jax.default_backend() == "tpu" and t_q % _Q_TILE == 0 \
            and keys.shape[0] % _K_TILE == 0 and keys.shape[1] % 128 == 0:
        # the kernel takes the keys at the lanes they are staged in: the
        # query's lanes past ID are zeros
        q = jnp.pad(q, ((0, 0), (0, 0), (0, keys.shape[1] - d)))
        return _scores_kernel(q, w, keys, first)
    positions = first + jnp.arange(t_q, dtype=jnp.int32)
    return _scores_tiles(q, w, keys[:, :d].astype(f32), positions)


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> uint32 in the same order (``-inf`` the least)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


# rows of scores a grid step of the cut's kernel holds in VMEM through all
# its passes: 32 x 26,624 float32 are 3.4 MB, and as much again as keys
_CUT_ROWS = 32


def _cut_kernel(x_ref, o_ref, key_ref, *, k):
    """The k-th largest key of every row of x_ref (rows, S) float32 ->
    o_ref (rows, 1) int32, in :func:`_ordered`'s order held as int32 (the
    unsigned pattern's bits): 32 counting passes over keys held in VMEM."""
    bits = lax.bitcast_convert_type(x_ref[...], jnp.int32)
    # a signed key in the floats' order; a candidate's unsigned pattern is
    # compared as ``pattern ^ sign``
    key_ref[...] = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    sign = jnp.int32(-2 ** 31)

    def bit(i, cut):
        cand = cut | (jnp.int32(1) << (jnp.int32(31) - i))
        count = jnp.sum((key_ref[...] >= (cand ^ sign)).astype(jnp.int32),
                        axis=1, keepdims=True)
        return jnp.where(count >= k, cand, cut)

    o_ref[...] = lax.fori_loop(0, 32, bit,
                               jnp.zeros(o_ref.shape, jnp.int32))


def _cut_rows(scores, k: int, *, interpret=False):
    """:func:`topk_mask`'s threshold a row as one Pallas call over tiles of
    rows: the scores are read once and every pass runs in VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    t, s_len = scores.shape
    rows = min(_CUT_ROWS, t)
    cut = pl.pallas_call(
        functools.partial(_cut_kernel, k=k),
        grid=(t // rows,),
        in_specs=[pl.BlockSpec((rows, s_len), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rows, s_len), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="index_topk_cut",
    )(scores)
    return lax.bitcast_convert_type(cut[:, 0], jnp.uint32)


def _cut_passes(u, k: int):
    """The same threshold in plain ``jax.numpy``: 32 passes over ``u``."""
    @jax.named_scope("index_topk")
    def bit(i, cut):
        cand = cut | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = (u >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, cut)

    return lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))


def topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """The exact top ``k`` of every row as a mask.

    scores (..., S) float32, ``-inf`` where a position is not a candidate
    (zeros +0.0, as :func:`index_scores` leaves them).  Returns (..., S)
    bool: the ``k`` candidates of largest score, all of them where a row has
    ``k`` or fewer, of equal scores the lower position first.

    No sort: the ``k``-th largest value is found bit by bit (32 passes that
    count the entries at or over a threshold; on a TPU, over whole tiles of
    rows, a Pallas kernel that reads the scores once and runs the passes in
    VMEM), and only a row whose cut falls among equal scores needs their
    order."""
    u = _ordered(scores)
    least = _ordered(jnp.float32(NEG_INF))
    # the greatest value that k entries or more reach (0: fewer than k)
    t = scores.shape[0]
    if jax.default_backend() == "tpu" and scores.ndim == 2 \
            and (t % _CUT_ROWS == 0 or t <= 8):
        with jax.named_scope("index_topk"):
            # a decode step's few rows fill a tile of 8, and its P + 1
            # scores whole lanes, with -inf
            pad = (-t % 8, -scores.shape[1] % 128)
            whole_tiles = scores if pad == (0, 0) else jnp.pad(
                scores, ((0, pad[0]), (0, pad[1])), constant_values=NEG_INF)
            cut = _cut_rows(whole_tiles, k)
            cut = cut[:t] if pad[0] else cut
    else:
        cut = _cut_passes(u, k)
    with jax.named_scope("index_topk"):
        live = u > least
        over = u > cut[..., None]
        tied = (u == cut[..., None]) & live
        room = k - over.sum(-1)                     # of the tied, how many
        whole = (tied.sum(-1) <= room).all()

    @jax.named_scope("index_topk")
    def by_position(_):
        return tied & (jnp.cumsum(tied, axis=-1) <= room[..., None])

    tied = lax.cond(whole, lambda _: tied, by_position, None)
    return (over & live) | tied


def prefill_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                      chosen: jax.Array, positions: jax.Array,
                      extent) -> jax.Array:
    """Softmax attention of a run of queries, each over its chosen
    positions.

    q (T, KV, R, D); k_all, v_all (S, KV * D) lane-flat float32, the staged
    K and V; chosen (T, S) bool (:func:`topk_mask`: nothing past a query's
    own position); positions (T,); extent: the run's end.  Returns (T, KV,
    R, D).  A chunk of queries against the whole staging cannot gather its
    rows (2,048 x 2,048 x 4 KB a layer): it is the flash pass over tiles of
    ``ops/sparse_attention.prefill_attention`` under a mask a position wide,
    which computes a tile no query of its tile chose nothing of, and masks
    the pairs not chosen."""
    kv = q.shape[1]
    mask = jnp.broadcast_to(chosen[None], (kv,) + chosen.shape)
    return sparse.prefill_attention(q, k_all, v_all, mask, positions, extent,
                                    block=1)


# ------------------------------------------------------------------- decode
def decode_scores(q: jax.Array, w: jax.Array, index_pool: jax.Array, layer,
                  block_tables: jax.Array, ctx_lens: jax.Array,
                  key_new: jax.Array) -> jax.Array:
    """One decode step's scores in one layer.

    q (B, IH, ID), w (B, IH); index_pool (L, 1, N, bs, F): the whole index
    plane, a key a position in its first ``ID`` lanes, read-only; layer:
    traced; block_tables (B, MAXB); ctx_lens (B,): positions in the pool, so
    the new token stands at ``ctx_lens``; key_new (B, ID): its own index
    key, which the pool does not hold yet.

    Returns (B, MAXB * bs + 1) float32: ``I`` at every cached position by
    its place in the context, ``-inf`` at ``ctx_lens`` and past it, and the
    new token's own score LAST.  The pages are gathered out of the plane
    where it lies (the layer's slab is not sliced out), by the table's
    whole width."""
    n_layer, _, n_blocks, bs, f = index_pool.shape
    b, _, d = q.shape
    f32 = jnp.float32
    q, w = q.astype(f32), w.astype(f32)
    pages = index_pool.reshape(n_layer * n_blocks, bs, f)[
        layer * n_blocks + block_tables]                  # (B, MAXB, bs, F)
    keys = pages.reshape(b, -1, f)[..., :d].astype(f32)
    s = jnp.einsum("bjd,bsd->bjs", q, keys, precision=_HI)
    cached = jnp.einsum("bjs,bj->bs", jnp.maximum(s, 0.0), w, precision=_HI)
    own = jnp.einsum("bjd,bd->bj", q, key_new.astype(f32), precision=_HI)
    own = (jnp.maximum(own, 0.0) * w).sum(-1)
    at = jnp.arange(keys.shape[1])
    cached = jnp.where(at[None, :] < ctx_lens[:, None], cached, NEG_INF)
    return _plus_zero(jnp.concatenate([cached, own[:, None]], axis=1))


def pool_rows(block_tables: jax.Array, block_size: int) -> jax.Array:
    """(B, MAXB) block tables -> (B, MAXB * bs + 1) int32: the pool row
    ``table[p // bs] * bs + p % bs`` of every position of a row's table in
    the order :func:`decode_scores` scores them, and -1 LAST, for the new
    token's own (it is in no pool yet)."""
    rows = block_tables[:, :, None] * block_size + jnp.arange(block_size)
    rows = rows.reshape(block_tables.shape[0], -1)
    return jnp.concatenate(
        [rows, jnp.full((rows.shape[0], 1), -1, rows.dtype)], axis=1)


def top_positions(scores: jax.Array, ctx_lens: jax.Array, k: int,
                  names: jax.Array = None):
    """The exact top ``k`` of a decode step's scores as a list a row.

    scores (B, P + 1) as :func:`decode_scores` returns them.  Returns
    (listed (B, k) int32: the chosen positions in rising order, the new
    token's own, the last, named as ``ctx_lens``; count (B,): the first
    ``count`` entries are chosen, as many as the cut took, ``min(ctx_lens
    + 1, k)``, the rest 0).  With ``names`` (B, P + 1) int32, a name an
    entry (:func:`pool_rows`), the list holds the chosen entries' NAMES in
    rising order instead, so that no table is looked up for them.

    The cut is :func:`topk_mask`'s, the one a run of queries takes: the
    ``k``-th largest score by counting, of equal scores at the cut the
    lower index, and the own score stands last, behind every cached one, so
    a tie with it goes the way a run of queries decides it.  The list is
    the chosen entries brought to the front by ONE sort of int32 keys (an
    entry not chosen sorts behind every one that is): the scores are not
    sorted, and no order among them is needed."""
    p = scores.shape[1] - 1
    k = min(k, p + 1)
    chosen = topk_mask(scores, k)
    behind = jnp.iinfo(jnp.int32).max
    named = lax.broadcasted_iota(jnp.int32, scores.shape, 1) \
        if names is None else names
    front = lax.sort(jnp.where(chosen, named, behind), dimension=1,
                     is_stable=False)[:, :k]
    if names is None:
        front = jnp.where(front == p, ctx_lens[:, None], front)
    count = chosen.sum(-1, dtype=jnp.int32)
    listed = jnp.arange(k)[None, :] < count[:, None]
    return jnp.where(listed, front, 0).astype(jnp.int32), count
