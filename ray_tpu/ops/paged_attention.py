"""Paged decode attention: one query token attending over a block table.

Reference design: PagedAttention (Kwon et al., SOSP '23 / vLLM) — the KV
cache of a running sequence is not one contiguous region but a list of
fixed-size *blocks* owned by an allocator; attention reads through a
per-sequence **block table** (block indices into a shared pool).  The
engine (``ray_tpu/serve/llm``) keeps the pool on the device, one array
that its programs take donated and hand back; replicas exchange blocks
by explicit copies over the data plane.

This module is the math, behind one call: ``paged_attention_decode``.
Two implementations of the same algorithm sit behind it, chosen from
what the code can see (the backend and the shapes), never by an option:

* ``_paged_decode_kernel`` — on a TPU.  A Pallas kernel that takes the
  block tables, the context lengths and the layer's index as prefetched
  scalars and the engine's whole pool where it lies in HBM, and,
  sequence by sequence, copies only the blocks the context holds
  (``ceil(ctx_len / bs)`` table columns) of that layer into VMEM,
  double-buffered 128 positions at a time, folding each chunk into an
  online softmax.  Table columns past the context cost no copy and no
  arithmetic; a row padded up to the decode bucket (``ctx_len == 0``)
  costs the new token's own term.  Mosaic copies whole 128-lane tiles,
  and the pool is stored so that a block is made of them (below):
  nothing is sliced, transposed or padded for the kernel.  With a
  position's heads side by side along the lanes all heads share two
  float32 matmuls a chunk, against a block-diagonal query.
* ``_paged_decode_gather`` — everywhere else (the CPU rig), and the
  tests' reference: slice the layer out, gather every table column as a
  padded dense view, attend, mask by ``ctx_lens``.  XLA fuses the chain;
  its cost is that of the table, not of the contexts.

A list of pages.  A layer that selects (``ops/sparse_attention.py``:
block-sparse attention that chooses its pages) hands the same call, beside
the table, ``pages`` (B, KV, K): for each row and KV head the table
COLUMNS it chose, and ``counts`` (B, KV): how many of them.  The kernel
then walks that list and not the table's first ``ceil(ctx_len / bs)``
columns, one (row, KV head) a grid step, and copies of a page the head's
own ``D`` lanes; a position's place in the context is its column's, so the
walk may be in any order.  The call without a list is the case "every
page of the context, all heads together": one kernel body, and which walk
is built follows from whether a list was given, never from an option.

A window.  A sliding-window layer (``models/afmoe.py``) hands the call
``window``: the new token sees that many positions, its own the last.  The
walk over the table then starts at the column that holds the first of
them and masks what lies before it in that column; the columns behind are
not read, and the cache has given their blocks back
(``serve/llm/kv_cache.py``, pages of two kinds).  A static argument: a
layer without one compiles to the walk it had.

A block of positions.  A model that generates by diffusion over blocks
(``models/llama.py``, ``block_length``) hands the call ``q (R, B, H, D)`` and
``k_new, v_new (R, B, KV, D)``: the B positions of a row's open block.  Its
B queries read the row's pages ONCE and see all B new keys and values, in
both directions (the mask inside a block is full); the pool holds the
committed positions before the block and is read-only here, as for one new
token.  On a TPU ``_block_decode_kernel``: a (row, KV head) a grid step,
the head's ``B x rep`` query rows against its own ``D`` lanes of a page;
elsewhere ``_block_decode_gather``, its oracle.  The walks above are not
touched by it.

A list of positions.  A layer whose indexer picks positions one by one
(``ops/indexed_attention.py``; ``models/llama.py``, ``index_topk``) calls
:func:`indexed_attention_decode` with ``positions`` (B, K), a row's chosen
positions in any order, and ``count`` (B,).  Where the listed form copies
the whole pages its columns name and the block form whole blocks, this one
reads the K and V ROWS of the positions it is given, ``(table[p // bs], p %
bs)``, out of the pool where it lies, and the new token's own where the
list names ``ctx_lens``: ``min(context, K)`` rows a KV plane whatever the
context.  A gather of rows by XLA on every backend: Mosaic copies no single
row of an HBM array (a row is part of a tile), and the 8 rows a row lies in
are, over chosen positions spread through a context, the whole context.

The pool's format, ``(L, 2, N, bs, F)`` (layer, K or V, block, position
in the block, the position's ``KV * D`` features flat along the lanes and
zero-padded to whole 128-lane tiles: 25 x 64 -> 1,664), belongs to its
writer (``serve/llm/kv_cache.py``: ``device_shape``, ``write_rows``) and
to this module, its reader.  The models' decode steps hand
``paged_attention_decode`` the pool whole and their scan's layer index,
and index no axis of it themselves: a ``pool[layer]`` with a traced
layer handed on as an operand would be a copy of the layer in every
step of the scan, the pass over the pool that this format removes.

Accumulators are float32 regardless of input dtype (bf16-safe softmax),
matching ``ops/attention.py``.  The pool is only read here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = jnp.finfo(jnp.float32).min

# positions copied into VMEM per step of a sequence's walk, as whole
# blocks: one buffer of K and one of V, each double-buffered (3.3 MiB
# in all at XL's 1,600 float32 lanes a position)
_CHUNK_TOKENS = 128
# and of a listed walk, whose pages are one head's lanes: 0.5 MiB of K and
# as much of V a buffer at D = 128
_LIST_CHUNK_TOKENS = 512


def gather_kv(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize each sequence's paged KV as a padded dense view.

    pool: (num_blocks, block_size, n_kv, d) — the shared block pool.
    block_tables: (B, max_blocks) int32 — indices into the pool; entries
        past a sequence's allocation may be arbitrary valid indices
        (masking is by context length, not by table entry).

    Returns (B, max_blocks * block_size, n_kv, d).
    """
    n, bs, kv, d = pool.shape
    b, mb = block_tables.shape
    with jax.named_scope("paged_gather"):
        g = jnp.take(pool, block_tables.reshape(-1), axis=0)
        return g.reshape(b, mb * bs, kv, d)


def lane_flat(x: jax.Array, width: int) -> jax.Array:
    """(..., KV, D) -> (..., width): a position's heads side by side
    along the lanes, zero-padded to ``width`` (the pool's ``F``)."""
    f = x.shape[-2] * x.shape[-1]
    flat = x.reshape(x.shape[:-2] + (f,))
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, width - f)])


def heads_apart(x: jax.Array, n_kv: int, head_dim: int) -> jax.Array:
    """``lane_flat`` undone: (..., F) -> (..., KV, D), the padding cut."""
    return x[..., :n_kv * head_dim].reshape(x.shape[:-1] + (n_kv, head_dim))


def _paged_decode_gather(q, kv_pool, layer, block_tables, ctx_lens,
                         k_new, v_new, pages=None, counts=None, window=None):
    """Gather-then-mask: the CPU path and the kernel's reference."""
    b, h, d = q.shape
    kvh = k_new.shape[1]
    if pages is not None:
        return _listed_decode_gather(q, kv_pool, layer, block_tables,
                                     ctx_lens, k_new, v_new, pages, counts)
    with jax.named_scope("kv_layout"):
        # the layer's K and V, each (N, bs, KV, D); here a slice costs
        # nothing that matters
        k_pool, v_pool = heads_apart(kv_pool[layer], kvh, d)
    scale = 1.0 / math.sqrt(d)
    if window is not None:
        # a column behind the window names no block (out of range): it is
        # masked below, and gathered from somewhere that exists
        block_tables = jnp.minimum(block_tables, k_pool.shape[0] - 1)
    k_ctx = gather_kv(k_pool, block_tables)          # (B, T, KV, D)
    v_ctx = gather_kv(v_pool, block_tables)
    t = k_ctx.shape[1]
    with jax.named_scope("paged_attention"):
        if kvh != h:                                 # grouped-query heads
            rep = h // kvh
            k_ctx = jnp.repeat(k_ctx, rep, axis=2)
            v_ctx = jnp.repeat(v_ctx, rep, axis=2)
            k_new = jnp.repeat(k_new, rep, axis=1)
            v_new = jnp.repeat(v_new, rep, axis=1)
        logits = jnp.einsum("bhd,bkhd->bhk", q, k_ctx,
                            preferred_element_type=jnp.float32) * scale
        valid = jnp.arange(t)[None, :] < ctx_lens[:, None]      # (B, T)
        if window is not None:
            # the new token stands at ctx_lens and sees ``window``
            # positions, its own the last
            valid &= jnp.arange(t)[None, :] > (ctx_lens - window)[:, None]
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
        self_logit = jnp.einsum("bhd,bhd->bh", q, k_new,
                                preferred_element_type=jnp.float32) * scale
        logits = jnp.concatenate([logits, self_logit[..., None]], axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)                 # f32
        out = jnp.einsum("bhk,bkhd->bhd", probs[..., :-1],
                         v_ctx.astype(jnp.float32))
        out = out + probs[..., -1][..., None] * v_new.astype(jnp.float32)
        return out.astype(q.dtype)


def _listed_decode_gather(q, kv_pool, layer, block_tables, ctx_lens,
                          k_new, v_new, pages, counts):
    """The gather path under a list of pages a (row, KV head): only the
    listed table columns are gathered, each head its own."""
    b, h, d = q.shape
    kvh, bs = k_new.shape[1], kv_pool.shape[3]
    rep, n_list = h // kvh, pages.shape[2]
    with jax.named_scope("kv_layout"):
        k_pool, v_pool = heads_apart(kv_pool[layer], kvh, d)  # (N,bs,KV,D)
    scale = 1.0 / math.sqrt(d)
    with jax.named_scope("paged_gather"):
        phys = jnp.take_along_axis(
            block_tables, pages.reshape(b, kvh * n_list), axis=1
        ).reshape(b, kvh, n_list)
        head = jnp.arange(kvh)[None, :, None]
        k_ctx = k_pool[phys, :, head]              # (B, KV, K, bs, D)
        v_ctx = v_pool[phys, :, head]
    with jax.named_scope("paged_attention"):
        f32 = jnp.float32
        pos = pages[..., None] * bs + jnp.arange(bs)            # (B,KV,K,bs)
        valid = (pos < ctx_lens[:, None, None, None]) & (
            jnp.arange(n_list)[None, None, :, None]
            < counts[:, :, None, None])
        qg = q.reshape(b, kvh, rep, d)
        logits = jnp.einsum("bgrd,bgkpd->bgrkp", qg, k_ctx,
                            preferred_element_type=f32) * scale
        logits = jnp.where(valid[:, :, None], logits, NEG_INF)
        logits = logits.reshape(b, kvh, rep, n_list * bs)
        self_logit = jnp.einsum("bgrd,bgd->bgr", qg, k_new,
                                preferred_element_type=f32) * scale
        logits = jnp.concatenate([logits, self_logit[..., None]], axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrk,bgkd->bgrd", probs[..., :-1],
                         v_ctx.astype(f32).reshape(b, kvh, n_list * bs, d))
        out = out + probs[..., -1:] * v_new.astype(f32)[:, :, None]
        return out.reshape(b, h, d).astype(q.dtype)


def _decode_kernel(tables_ref, lens_ref, layer_ref, *refs, head_dim,
                   kv_rows, listed, window=None):
    """One sequence (grid step): walk its blocks, a chunk at a time.

    Heads lie along the lanes: a block is (bs, F), F = KV * D padded to
    whole tiles.  q_ref (1, R, F) holds one row a query head, zero
    outside its KV head's D lanes (block-diagonal, pre-scaled), so one
    matmul against a chunk's keys (T, F) gives every head's scores
    (R, T), and probabilities @ values (T, F) every head's result in its
    own lanes of (R, F).  Rows are ordered (group member, KV head), each
    group member's KV heads padded to ``kv_rows``.  pool_hbm
    (L, 2, N, bs, F) is left where it is and copied from by block,
    ``pool_hbm[layer, 0 / 1, table[j]]``, each contiguous whole tiles;
    k_buf / v_buf (2, C, bs, F) are the VMEM landing buffers; sems
    (2, 2): [k|v, buffer].

    ``listed``: the walk is over a list of table columns a (row, KV head)
    and a grid step is one such pair: two more prefetched scalars lead
    ``refs`` (pages (B, KV, K), counts (B, KV)), q_ref (1, 1, rep, D) holds
    the head's query group as it is, a page's copy is the head's own D
    lanes, ``pool_hbm[layer, 0 / 1, table[pages[j]], :, g D : g D + D]``,
    and a position's place in the context is its column's.

    ``window`` (the walk over a table, not a list): the new token sees
    that many positions, its own the last, so the walk starts at the table
    column that holds position ``ctx - window + 1`` and the positions of
    that column before it are masked; the columns behind it are never
    read, and may name no block any more.
    """
    from jax.experimental.pallas import tpu as pltpu

    if listed:
        pages_ref, counts_ref, *refs = refs
    (q_ref, k_new_ref, v_new_ref, pool_hbm, o_ref, k_buf, v_buf, sems,
     m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    _, chunk, bs, f = k_buf.shape
    t = chunk * bs
    ctx = lens_ref[b]
    layer = layer_ref[0]
    if listed:
        g = pl.program_id(1)
        n_blocks = counts_ref[b, g]
        lanes = pl.ds(pl.multiple_of(g * head_dim, head_dim), head_dim)
    else:
        n_blocks = pl.cdiv(ctx, bs)
    # the first position the new token sees and the column that holds it
    lo = col0 = 0
    if window is not None:
        lo = jnp.maximum(ctx - (window - 1), 0)
        col0 = lo // bs
        n_blocks = n_blocks - col0
    n_chunks = pl.cdiv(n_blocks, chunk)
    hi = lax.Precision.HIGHEST      # float32 K/V stay float32 on the MXU

    def column(j):
        """The table column of the walk's j-th page, clamped: the read
        stays inside the row when the guard fails."""
        if listed:
            j = pages_ref[b, g, jnp.minimum(j, pages_ref.shape[2] - 1)]
        if window is not None:
            j = j + col0
        return jnp.minimum(j, tables_ref.shape[1] - 1)

    def page(kv, blk):
        if listed:
            return pool_hbm.at[layer, kv, blk, :, lanes]
        return pool_hbm.at[layer, kv, blk]

    def copies(c, slot):
        """The chunk's copies, each with the guard it runs under."""
        out = []
        for i in range(chunk):
            j = c * chunk + i
            blk = tables_ref[b, column(j)]
            out.append((j < n_blocks, (
                pltpu.make_async_copy(page(0, blk),
                                      k_buf.at[slot, i], sems.at[0, slot]),
                pltpu.make_async_copy(page(1, blk),
                                      v_buf.at[slot, i], sems.at[1, slot]))))
        return out

    def start(c, slot):
        for live, (ck, cv) in copies(c, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(c, slot):
        for live, (ck, cv) in copies(c, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    def live_positions(c, shape, axis):
        """Which of the chunk's T positions, laid along ``axis`` of
        ``shape``, are in the context."""
        if not listed and window is None:
            return c * t + lax.broadcasted_iota(jnp.int32, shape, axis) < ctx
        if not listed:
            at = col0 * bs + c * t \
                + lax.broadcasted_iota(jnp.int32, shape, axis)
            return (at < ctx) & (at >= lo)
        at = lax.broadcasted_iota(jnp.int32, shape, axis)
        # a listed page lies where its column says, and past the count
        # nothing was copied
        nth = at // bs
        first = jnp.zeros(shape, jnp.int32)
        for i in range(chunk):
            first = jnp.where(nth == i, column(c * chunk + i) * bs, first)
        return (first + at % bs < ctx) & (c * chunk + nth < n_blocks)

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    # the new token's own term seeds the running softmax: max = its
    # score, sum = 1, accumulator = v_new.  A padded row ends here.
    q = q_ref[0, 0] if listed else q_ref[0]                     # (R, F)
    k_new = k_new_ref[0, 0] if listed else k_new_ref[0]
    m_ref[...] = jnp.sum(q * k_new, axis=-1, keepdims=True)
    l_ref[...] = jnp.ones_like(l_ref)
    v_new = v_new_ref[0, 0] if listed else v_new_ref[0]
    acc_ref[...] = jnp.broadcast_to(v_new, acc_ref.shape)

    @pl.loop(0, n_chunks)
    def _(c):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        k = k_buf[slot].reshape(t, f)
        v = v_buf[slot].reshape(t, f)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=hi,
                            preferred_element_type=jnp.float32)  # (R, T)
        # what was not copied, and the last block's tail, may hold
        # anything: select, never multiply by zero
        s = jnp.where(live_positions(c, (1, t), 1), s, NEG_INF)
        v = jnp.where(live_positions(c, (t, 1), 0), v, 0.0)
        m = m_ref[...]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        p = jnp.exp(s - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v, precision=hi, preferred_element_type=jnp.float32)

    out = acc_ref[...] / l_ref[...]
    if listed:
        o_ref[0, 0] = out.astype(o_ref.dtype)
        return
    # row (r, kv) keeps the D lanes of KV head kv; the rows of one group
    # member then sum to its (1, F) result
    head = lax.broadcasted_iota(jnp.int32, (kv_rows, f), 1) // head_dim
    own = head == lax.broadcasted_iota(jnp.int32, (kv_rows, f), 0)
    for r in range(o_ref.shape[1]):
        part = out[r * kv_rows:(r + 1) * kv_rows]
        o_ref[0, r] = jnp.sum(jnp.where(own, part, 0.0), axis=0
                              ).astype(o_ref.dtype)


def _paged_decode_kernel(q, kv_pool, layer, block_tables, ctx_lens,
                         k_new, v_new, pages=None, counts=None, *,
                         window=None, interpret=False):
    """The block-table walk as one Pallas call over the batch (with
    ``pages``: over the batch's (row, KV head) pairs)."""
    # imported where the kernel is built (flash_attention's idiom), so
    # that importing ray_tpu.ops costs a training process nothing more
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    bs, f = kv_pool.shape[3:]
    kvh = k_new.shape[1]
    rep, f32 = h // kvh, jnp.float32
    if pages is not None:
        return _listed_decode_kernel(q, kv_pool, layer, block_tables,
                                     ctx_lens, k_new, v_new, pages, counts,
                                     interpret=interpret)
    kv_rows = -(-kvh // 8) * 8
    chunk = max(1, min(_CHUNK_TOKENS // bs, block_tables.shape[1]))
    with jax.named_scope("paged_attention"):
        # head h reads KV head h // rep (jnp.repeat's order).  Row
        # (r, kv) of the query operand: q[kv * rep + r], scaled, in
        # lanes [kv * D, (kv + 1) * D), zero elsewhere
        q4 = q.astype(f32).reshape(b, kvh, rep, d).transpose(0, 2, 1, 3)
        q4 = q4 * (1.0 / math.sqrt(d))
        qbd = lane_flat(q4[:, :, :, None, :]
                        * jnp.eye(kvh, dtype=f32)[:, :, None], f)
        qbd = jnp.pad(qbd, ((0, 0), (0, 0), (0, kv_rows - kvh), (0, 0)))
        row = lambda i, tables, lens, layer: (i, 0, 0)         # noqa: E731
        out = pl.pallas_call(
            functools.partial(_decode_kernel, head_dim=d, kv_rows=kv_rows,
                              listed=False, window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec((1, rep * kv_rows, f), row),
                    pl.BlockSpec((1, 1, f), row),
                    pl.BlockSpec((1, 1, f), row),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, rep, f), row),
                scratch_shapes=[
                    pltpu.VMEM((2, chunk, bs, f), kv_pool.dtype),
                    pltpu.VMEM((2, chunk, bs, f), kv_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((rep * kv_rows, 1), f32),
                    pltpu.VMEM((rep * kv_rows, 1), f32),
                    pltpu.VMEM((rep * kv_rows, f), f32),
                ]),
            out_shape=jax.ShapeDtypeStruct((b, rep, f), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_decode" if window is None else "paged_decode_window",
        )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          qbd.reshape(b, rep * kv_rows, f),
          lane_flat(k_new.astype(f32), f)[:, None],
          lane_flat(v_new.astype(f32), f)[:, None], kv_pool)
        out = heads_apart(out, kvh, d).reshape(b, rep, kvh, d)
        return out.transpose(0, 2, 1, 3).reshape(b, h, d)


def _listed_decode_kernel(q, kv_pool, layer, block_tables, ctx_lens, k_new,
                          v_new, pages, counts, *, interpret=False):
    """The same kernel body walking each (row, KV head)'s list of pages:
    a grid step a pair, the head's query group (rep, D) as it is, a
    page's copy the head's own D lanes."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    bs = kv_pool.shape[3]
    kvh = k_new.shape[1]
    rep, f32 = h // kvh, jnp.float32
    chunk = max(1, min(_LIST_CHUNK_TOKENS // bs, pages.shape[2]))
    with jax.named_scope("paged_attention"):
        qg = q.astype(f32).reshape(b, kvh, rep, d) * (1.0 / math.sqrt(d))
        pair = lambda i, g, *prefetched: (i, g, 0, 0)          # noqa: E731
        out = pl.pallas_call(
            functools.partial(_decode_kernel, head_dim=d, kv_rows=1,
                              listed=True),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(b, kvh),
                in_specs=[
                    pl.BlockSpec((1, 1, rep, d), pair),
                    pl.BlockSpec((1, 1, 1, d), pair),
                    pl.BlockSpec((1, 1, 1, d), pair),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, 1, rep, d), pair),
                scratch_shapes=[
                    pltpu.VMEM((2, chunk, bs, d), kv_pool.dtype),
                    pltpu.VMEM((2, chunk, bs, d), kv_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((rep, 1), f32),
                    pltpu.VMEM((rep, 1), f32),
                    pltpu.VMEM((rep, d), f32),
                ]),
            out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="paged_decode_listed",
        )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          pages.astype(jnp.int32), counts.astype(jnp.int32), qg,
          k_new.astype(f32)[:, :, None], v_new.astype(f32)[:, :, None],
          kv_pool)
        return out.reshape(b, h, d)


# ------------------------------------------------------- a block of positions
def _block_decode_gather(q, kv_pool, layer, block_tables, ctx_lens, k_new,
                         v_new):
    """Gather-then-mask for a block of B positions a row: the CPU path and
    the block kernel's reference.  Every query of the block sees the row's
    ``ctx_lens`` pooled positions and all B new ones."""
    r, b, h, d = q.shape
    kvh = k_new.shape[2]
    rep, f32 = h // kvh, jnp.float32
    with jax.named_scope("kv_layout"):
        k_pool, v_pool = heads_apart(kv_pool[layer], kvh, d)
    k_ctx = gather_kv(k_pool, block_tables)          # (R, T, KV, D)
    v_ctx = gather_kv(v_pool, block_tables)
    t = k_ctx.shape[1]
    with jax.named_scope("paged_attention"):
        qg = q.reshape(r, b, kvh, rep, d)
        scale = 1.0 / math.sqrt(d)
        pooled = jnp.einsum("rbgpd,rtgd->rbgpt", qg, k_ctx,
                            preferred_element_type=f32) * scale
        valid = jnp.arange(t)[None, :] < ctx_lens[:, None]      # (R, T)
        pooled = jnp.where(valid[:, None, None, None, :], pooled, NEG_INF)
        own = jnp.einsum("rbgpd,rcgd->rbgpc", qg, k_new,
                         preferred_element_type=f32) * scale
        probs = jax.nn.softmax(jnp.concatenate([pooled, own], -1), axis=-1)
        out = jnp.einsum("rbgpt,rtgd->rbgpd", probs[..., :t],
                         v_ctx.astype(f32)) \
            + jnp.einsum("rbgpc,rcgd->rbgpd", probs[..., t:],
                         v_new.astype(f32))
        return out.reshape(r, b, h, d).astype(q.dtype)


def _block_kernel(tables_ref, lens_ref, layer_ref, q_ref, k_new_ref,
                  v_new_ref, pool_hbm, o_ref, k_buf, v_buf, sems, m_ref,
                  l_ref, acc_ref, *, head_dim, block):
    """One (row, KV head) (grid step): the head's ``block x rep`` query rows
    (q_ref (1, 1, Q, D), pre-scaled, position-major) against the row's
    pages, a chunk at a time, each page's copy the head's own D lanes:
    ``pool_hbm[layer, 0 / 1, table[j], :, g D : g D + D]``.  The block's own
    ``block`` keys and values (k_new_ref, v_new_ref (1, 1, 8, D), the rows
    past ``block`` padding) seed the running softmax: every query sees all
    of them."""
    from jax.experimental.pallas import tpu as pltpu

    r, g = pl.program_id(0), pl.program_id(1)
    _, chunk, bs, d = k_buf.shape
    t = chunk * bs
    ctx = lens_ref[r]
    layer = layer_ref[0]
    n_blocks = pl.cdiv(ctx, bs)
    n_chunks = pl.cdiv(n_blocks, chunk)
    lanes = pl.ds(pl.multiple_of(g * head_dim, head_dim), head_dim)
    hi = lax.Precision.HIGHEST      # float32 K/V stay float32 on the MXU

    def copies(c, slot):
        out = []
        for i in range(chunk):
            j = c * chunk + i
            blk = tables_ref[r, jnp.minimum(j, tables_ref.shape[1] - 1)]
            out.append((j < n_blocks, (
                pltpu.make_async_copy(pool_hbm.at[layer, 0, blk, :, lanes],
                                      k_buf.at[slot, i], sems.at[0, slot]),
                pltpu.make_async_copy(pool_hbm.at[layer, 1, blk, :, lanes],
                                      v_buf.at[slot, i], sems.at[1, slot]))))
        return out

    def start(c, slot):
        for live, (ck, cv) in copies(c, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(c, slot):
        for live, (ck, cv) in copies(c, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    # the block's own positions, one at a time on the VPU: a (Q, D) x
    # (block, D) product is too narrow a matmul to ask of the MXU
    q = q_ref[0, 0]                                             # (Q, D)
    own = [jnp.sum(q * k_new_ref[0, 0, pl.ds(c, 1), :], axis=-1,
                   keepdims=True) for c in range(block)]        # (Q, 1) each
    m = functools.reduce(jnp.maximum, own)
    p_own = [jnp.exp(s - m) for s in own]
    m_ref[...] = m
    l_ref[...] = functools.reduce(jnp.add, p_own)
    acc_ref[...] = functools.reduce(jnp.add, [
        p * v_new_ref[0, 0, pl.ds(c, 1), :] for c, p in enumerate(p_own)])

    @pl.loop(0, n_chunks)
    def _(c):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        k = k_buf[slot].reshape(t, d)
        v = v_buf[slot].reshape(t, d)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=hi,
                            preferred_element_type=jnp.float32)  # (Q, T)
        # what was not copied, and the last page's tail, may hold
        # anything: select, never multiply by zero
        s = jnp.where(
            c * t + lax.broadcasted_iota(jnp.int32, (1, t), 1) < ctx, s,
            NEG_INF)
        v = jnp.where(
            c * t + lax.broadcasted_iota(jnp.int32, (t, 1), 0) < ctx, v, 0.0)
        m = m_ref[...]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        p = jnp.exp(s - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v, precision=hi, preferred_element_type=jnp.float32)

    o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _block_decode_kernel(q, kv_pool, layer, block_tables, ctx_lens, k_new,
                         v_new, *, interpret=False):
    """The walk for a block of positions a row as one Pallas call over the
    batch's (row, KV head) pairs: a row's pages are read once a pass for
    all its B positions."""
    from jax.experimental.pallas import tpu as pltpu

    r, b, h, d = q.shape
    bs = kv_pool.shape[3]
    kvh = k_new.shape[2]
    rep, f32 = h // kvh, jnp.float32
    rows = b * rep
    chunk = max(1, min(_LIST_CHUNK_TOKENS // bs, block_tables.shape[1]))
    with jax.named_scope("paged_attention"):
        # the head's query rows, position-major: row (c, p) is position c
        # of the block, member p of the KV head's group
        qg = q.astype(f32).reshape(r, b, kvh, rep, d).transpose(0, 2, 1, 3, 4)
        qg = qg.reshape(r, kvh, rows, d) * (1.0 / math.sqrt(d))

        def own(x):                                   # (R, B, KV, D)
            x = x.astype(f32).transpose(0, 2, 1, 3)
            return jnp.pad(x, ((0, 0), (0, 0), (0, -b % 8), (0, 0)))
        pair = lambda i, g, *prefetched: (i, g, 0, 0)          # noqa: E731
        new_rows = b + -b % 8
        out = pl.pallas_call(
            functools.partial(_block_kernel, head_dim=d, block=b),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(r, kvh),
                in_specs=[
                    pl.BlockSpec((1, 1, rows, d), pair),
                    pl.BlockSpec((1, 1, new_rows, d), pair),
                    pl.BlockSpec((1, 1, new_rows, d), pair),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, 1, rows, d), pair),
                scratch_shapes=[
                    pltpu.VMEM((2, chunk, bs, d), kv_pool.dtype),
                    pltpu.VMEM((2, chunk, bs, d), kv_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((rows, 1), f32),
                    pltpu.VMEM((rows, 1), f32),
                    pltpu.VMEM((rows, d), f32),
                ]),
            out_shape=jax.ShapeDtypeStruct((r, kvh, rows, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="paged_decode_block",
        )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), qg, own(k_new),
          own(v_new), kv_pool)
        out = out.reshape(r, kvh, b, rep, d).transpose(0, 2, 1, 3, 4)
        return out.reshape(r, b, h, d)


def paged_attention_decode(q: jax.Array, kv_pool: jax.Array, layer,
                           block_tables: jax.Array, ctx_lens: jax.Array,
                           k_new: jax.Array, v_new: jax.Array,
                           pages: jax.Array = None,
                           counts: jax.Array = None,
                           window: int = None) -> jax.Array:
    """Single-token decode attention through a block table.

    q:       (B, H, D)        — query for the token being decoded.
    kv_pool: (L, 2, N, bs, F) — the engine's whole pool
                                (``kv_cache.device_shape``), read-only.
    layer:   int32 scalar     — whose K and V to read; traced inside the
                                models' layer scans.
    block_tables: (B, MAXB) int32.
    ctx_lens: (B,) int32      — tokens already IN the pool per sequence
                                (the new token is not in the pool yet).
    k_new, v_new: (B, KV, D)  — this token's key/value, attended in
                                explicitly: the pool is only read here,
                                and the runner's program writes the new
                                K/V into it after these reads.
    pages, counts: (B, KV, K), (B, KV) int32 — a layer that selects: the
                                table columns each (row, KV head) reads
                                (its first ``counts`` entries, in any
                                order) in place of every column of the
                                context.
    window: int               — a sliding-window layer: the new token sees
                                that many positions, its own the last; the
                                table's columns wholly behind them are not
                                read (their blocks may have gone back).

    Returns (B, H, D) in q.dtype.  On a TPU the blocks a context holds
    are all that is read; elsewhere every table column is gathered.

    A block of positions a row (a model that generates by diffusion over
    blocks): q (R, B, H, D), k_new, v_new (R, B, KV, D), ``ctx_lens`` the
    committed positions the pool holds BEFORE the block; every query of the
    block sees those and all B new keys and values.  Returns (R, B, H, D).
    """
    if q.ndim == 4:
        if pages is not None or window is not None:
            raise NotImplementedError(
                "a block of positions under a list of pages or a window")
        if jax.default_backend() == "tpu" and kv_pool.dtype == jnp.float32 \
                and q.shape[2] % k_new.shape[2] == 0 \
                and q.shape[3] % 128 == 0:
            return _block_decode_kernel(q, kv_pool, layer, block_tables,
                                        ctx_lens, k_new, v_new)
        return _block_decode_gather(q, kv_pool, layer, block_tables,
                                    ctx_lens, k_new, v_new)
    # the kernel is built for whole query groups per KV head and a
    # float32 pool, as the engine's is
    # (a listed walk copies a head's own lanes: whole tiles of them)
    if jax.default_backend() == "tpu" and kv_pool.dtype == jnp.float32 \
            and q.shape[1] % k_new.shape[1] == 0 \
            and (pages is None or q.shape[2] % 128 == 0):
        return _paged_decode_kernel(q, kv_pool, layer, block_tables,
                                    ctx_lens, k_new, v_new, pages, counts,
                                    window=window)
    return _paged_decode_gather(q, kv_pool, layer, block_tables, ctx_lens,
                                k_new, v_new, pages, counts, window)


# ------------------------------------------------------------- latent rows
# A latent page (``serve/llm/kv_cache.py``, the latent row): a layer of
# multi-head latent attention caches ONE row a position, ``[c | k_rope]``,
# in a pool of one plane, ``(L, 1, N, bs, F)``.  Absorbed, every query head
# scores that row whole (its first ``value_lanes`` lanes through the
# up-projection folded into the query, the rest the rotary key) and sums
# its first ``value_lanes`` lanes as the value: multi-query attention whose
# pages are read once for keys and values.  The same two paths as above,
# chosen the same way.
_LATENT_CHUNK_TOKENS = 256


def _latent_decode_gather(q, pool, layer, block_tables, ctx_lens, row_new,
                          value_lanes):
    """Gather-then-mask: the CPU path and the kernel's reference."""
    f32 = jnp.float32
    with jax.named_scope("paged_gather"):
        rows = jnp.take(pool[layer, 0], block_tables.reshape(-1), axis=0)
        rows = rows.reshape(q.shape[0], -1, pool.shape[-1])     # (B, T, F)
    with jax.named_scope("paged_attention"):
        logits = jnp.einsum("bhf,btf->bht", q, rows,
                            preferred_element_type=f32,
                            precision=lax.Precision.HIGHEST)
        valid = jnp.arange(rows.shape[1])[None, :] < ctx_lens[:, None]
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
        own = jnp.einsum("bhf,bf->bh", q, row_new,
                         preferred_element_type=f32,
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(
            jnp.concatenate([logits, own[..., None]], axis=-1), axis=-1)
        values = jnp.where(valid[:, :, None], rows[..., :value_lanes], 0.0)
        out = jnp.einsum("bht,btv->bhv", probs[..., :-1], values.astype(f32),
                         precision=lax.Precision.HIGHEST)
        return out + probs[..., -1:] * row_new[:, None, :value_lanes]


def _latent_kernel(tables_ref, lens_ref, layer_ref, q_ref, new_ref, pool_hbm,
                   o_ref, buf, sems, m_ref, l_ref, acc_ref, *, low):
    """One sequence (grid step): walk its latent pages, a chunk at a time.
    q_ref (1, H, F) the absorbed queries, pre-scaled, zero in the padding
    lanes; new_ref (1, 1, F) the new token's own row; pool_hbm (L, 1, N,
    bs, F) left where it is; buf (2, C, bs, F) the landing buffers.  A
    page's rows are keys and values at once: the accumulator keeps all F
    lanes and the caller cuts the value's.  ``low``: the type the two
    products take their operands in (the rows were made in it)."""
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    _, chunk, bs, f = buf.shape
    t = chunk * bs
    ctx = lens_ref[b]
    layer = layer_ref[0]
    n_blocks = pl.cdiv(ctx, bs)
    n_chunks = pl.cdiv(n_blocks, chunk)
    precision = lax.Precision.HIGHEST if low == jnp.float32 else None

    def copies(c, slot):
        out = []
        for i in range(chunk):
            j = c * chunk + i
            blk = tables_ref[b, jnp.minimum(j, tables_ref.shape[1] - 1)]
            out.append((j < n_blocks, pltpu.make_async_copy(
                pool_hbm.at[layer, 0, blk], buf.at[slot, i], sems.at[slot])))
        return out

    def start(c, slot):
        for live, copy in copies(c, slot):
            @pl.when(live)
            def _():
                copy.start()

    def wait(c, slot):
        for live, copy in copies(c, slot):
            @pl.when(live)
            def _():
                copy.wait()

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    q = q_ref[0]                                                # (H, F)
    new = new_ref[0]                                            # (1, F)
    m_ref[...] = jnp.sum(q * new, axis=-1, keepdims=True)
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.broadcast_to(new, acc_ref.shape)
    q_low = q.astype(low)

    @pl.loop(0, n_chunks)
    def _(c):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        # what was not copied, and the last page's tail, may hold anything:
        # select, never multiply by zero
        live = c * t + lax.broadcasted_iota(jnp.int32, (t, 1), 0) < ctx
        rows = jnp.where(live, buf[slot].reshape(t, f), 0.0).astype(low)
        s = lax.dot_general(q_low, rows, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)  # (H, T)
        s = jnp.where(
            c * t + lax.broadcasted_iota(jnp.int32, (1, t), 1) < ctx, s,
            NEG_INF)
        m = m_ref[...]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        p = jnp.exp(s - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(low), rows, precision=precision,
            preferred_element_type=jnp.float32)

    o_ref[0] = acc_ref[...] / l_ref[...]


def _latent_decode_kernel(q, pool, layer, block_tables, ctx_lens, row_new,
                          value_lanes, *, interpret=False):
    """The walk over latent pages as one Pallas call over the batch."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, f = q.shape
    bs = pool.shape[3]
    f32 = jnp.float32
    low = jnp.bfloat16 if q.dtype == jnp.bfloat16 else f32
    chunk = max(1, min(_LATENT_CHUNK_TOKENS // bs, block_tables.shape[1]))
    row = lambda i, tables, lens, layer: (i, 0, 0)             # noqa: E731
    with jax.named_scope("paged_attention"):
        out = pl.pallas_call(
            functools.partial(_latent_kernel, low=low),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec((1, h, f), row),
                    pl.BlockSpec((1, 1, f), row),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, h, f), row),
                scratch_shapes=[
                    pltpu.VMEM((2, chunk, bs, f), pool.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.VMEM((h, 1), f32),
                    pltpu.VMEM((h, 1), f32),
                    pltpu.VMEM((h, f), f32),
                ]),
            out_shape=jax.ShapeDtypeStruct((b, h, f), f32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_decode_latent",
        )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), q.astype(f32),
          row_new.astype(f32)[:, None], pool)
        return out[..., :value_lanes]


def latent_attention_decode(q: jax.Array, latent_pool: jax.Array, layer,
                            block_tables: jax.Array, ctx_lens: jax.Array,
                            row_new: jax.Array, value_lanes: int) -> jax.Array:
    """Single-token absorbed latent attention through a block table.

    q:           (B, H, R)        — each head's absorbed query ``[q_nope
                                    W_uk | q_rope]``, SCALED by the caller;
                                    its type says what the products run in.
    latent_pool: (L, 1, N, bs, F) — the engine's latent pool
                                    (``kv_cache.device_shape(..., planes=1)``),
                                    a position a row ``[c | k_rope]`` of R
                                    lanes zero-padded to F; read-only.
    layer:       int              — which latent layer's pages.
    row_new:     (B, R)           — this token's own row, attended in
                                    explicitly (the runner writes it after).
    value_lanes: the row's first lanes that are the value (``c``).

    Returns (B, H, value_lanes) float32: ``sum_t p_t c_t`` a head, which
    the caller takes through its ``W_uv``."""
    f = latent_pool.shape[-1]
    pad = [(0, 0)] * (q.ndim - 1) + [(0, f - q.shape[-1])]
    q, row_new = jnp.pad(q, pad), jnp.pad(row_new, pad[1:])
    if jax.default_backend() == "tpu" and latent_pool.dtype == jnp.float32:
        return _latent_decode_kernel(q, latent_pool, layer, block_tables,
                                     ctx_lens, row_new, value_lanes)
    return _latent_decode_gather(q.astype(jnp.float32), latent_pool, layer,
                                 block_tables, ctx_lens,
                                 row_new.astype(jnp.float32), value_lanes)


# --------------------------------------------------------- listed positions
def indexed_attention_decode(q: jax.Array, kv_pool: jax.Array, layer,
                             block_tables: jax.Array, ctx_lens: jax.Array,
                             k_new: jax.Array, v_new: jax.Array,
                             positions: jax.Array, count: jax.Array,
                             rows: jax.Array = None) -> jax.Array:
    """Single-token decode attention over a LIST OF POSITIONS a row.

    q (B, H, D); kv_pool (L, 2, N, bs, F), read-only; layer: traced;
    block_tables (B, MAXB); ctx_lens (B,): positions in the pool; k_new,
    v_new (B, KV, D): the new token's, which stands at ``ctx_lens``;
    positions (B, K) int32: the positions the row's query attends to, in
    any order, ``ctx_lens`` naming the new token itself (it is attended to
    only if listed); count (B,): the first ``count`` entries are real;
    rows (B, K) int32, where the caller has them (``positions`` may then be
    None): each listed position's row of a layer's K (or V) slab,
    ``table[p // bs] * bs + p % bs``, and -1 for the new token's own
    (``ops/indexed_attention.pool_rows``, which the list was made of: the
    table is then not looked up again, 8,192 scalar reads a layer at the
    Keye cell's shape).

    Returns (B, H, D) in q.dtype.  What is read of the pool is the listed
    positions' rows of K and of V, gathered by row number out of the pool
    taken as ``L x 2 x N x bs`` rows of ``F`` (as ``kv_cache.write_rows``
    writes them): the layer's slab is not sliced out and no page is copied
    whole."""
    n_layer, _, n_blocks, bs, f = kv_pool.shape
    b, h, d = q.shape
    kvh = k_new.shape[1]
    f32 = jnp.float32
    per_slab = n_blocks * bs
    with jax.named_scope("paged_gather"):
        if rows is None:
            own = positions == ctx_lens[:, None]                   # (B, K)
            blocks = jnp.take_along_axis(
                block_tables, jnp.minimum(positions // bs,
                                          block_tables.shape[1] - 1), axis=1)
            rows = blocks * bs + positions % bs
        else:
            own = rows < 0
        row = jnp.clip(rows, 0, per_slab - 1)
        flat = kv_pool.reshape(-1, f)
        k_sel = flat[(2 * layer) * per_slab + row]                  # (B, K, F)
        v_sel = flat[(2 * layer + 1) * per_slab + row]
        k_sel = jnp.where(own[..., None], lane_flat(k_new, f)[:, None]
                          .astype(f32), k_sel)
        v_sel = jnp.where(own[..., None], lane_flat(v_new, f)[:, None]
                          .astype(f32), v_sel)
    with jax.named_scope("paged_attention"):
        k_sel, v_sel = heads_apart(k_sel, kvh, d), heads_apart(v_sel, kvh, d)
        # float32 products at full precision: for a product that rounds
        # its operands to bf16 the compiler converts the WHOLE pool to
        # bf16, once a step outside the layers' loop, and gathers from
        # that (1.3 GB made a step at the Keye cell's pool: seen in its
        # first compile); the products here are 2,048 positions a row
        hi = lax.Precision.HIGHEST
        qg = q.reshape(b, kvh, h // kvh, d).astype(f32)
        logits = jnp.einsum("bgrd,bkgd->bgrk", qg, k_sel, precision=hi
                            ) * (1.0 / math.sqrt(d))
        live = jnp.arange(row.shape[1])[None, :] < count[:, None]
        logits = jnp.where(live[:, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrk,bkgd->bgrd", probs, v_sel, precision=hi)
        return out.reshape(b, h, d).astype(q.dtype)
