"""Paged KV cache: a device-resident block pool + per-sequence block tables.

Layout (PagedAttention, Kwon et al. SOSP '23): the cache is ONE device
array, held in the form its reader copies (:func:`device_shape`)::

    pool[n_layer, 2, num_blocks, block_size, F]

Blocks-major and lane-flat: a position's heads lie side by side along the
lanes, ``n_kv * head_dim`` of them zero-padded up to whole 128-lane tiles
(GPT-2 XL: 25 x 64 = 1,600 -> 1,664; Falcon-H1: 4 x 128 = 512, none), so
one block of one layer's K (or V) is ``(block_size, F)``: contiguous whole
tiles.  The decode kernel (``ops/paged_attention.py``) copies exactly
those out of the pool where it lies, by layer and block table, and a
token's K/V is one row of each: a write touches the rows it writes
(:func:`write_rows`) and nothing is re-laid for the kernel.  Held
blocks-first with the heads apart, ``(N, L, 2, bs, KV, D)``, the TPU
compiler put the block index on the lanes: every write was a pass over
the pool and every layer of every step a re-laid copy (PERF.md, PR 35).

On the wire a block is another thing: ``block_shape`` = ``(n_layer, 2,
block_size, n_kv, head_dim)``, one contiguous, self-describing slab of
``block_nbytes`` without the lane padding, the data-plane export unit
(``engine.prefill_remote`` / ``attach``).  :meth:`PagedKVCache.block_bytes`
gathers ``pool[:, :, i]`` into it and :meth:`PagedKVCache.load_block`
does the reverse; exports are rare and off the decode path, so the
format that crosses replicas does not follow the device's tiles.
:func:`device_shape`, :func:`write_rows` and the two programs behind those
methods are all that know the device format here.

The pool never leaves the device.  Every write to it is a jitted program
that takes the array donated and hands it back (:class:`DevicePool`):
the decode step writes its own new token (``model_runner.py``, through
:func:`write_rows`), prefill scatters a prompt's K/V, ``attach`` loads
imported blocks.  What crosses the host link is what the host asks for
by name: a decode step's logits, and exported or imported blocks
(``PagedKVCache.host_bytes`` counts the latter; the engine's own loop
leaves it at 0).  A device pool dies with its process: there is no
segment to unlink and nothing to reap.

The host keeps what is host work: the allocator hands out block indices
(free list), tracks a block table and a refcount per sequence, and frees
in block grains — preemption under cache pressure returns exactly the
preempted sequence's blocks.  Shared blocks (an attached sequence
re-exported, future prefix caching) are refcounted: ``free_seq`` returns
a block to the free list only at refcount zero.

The planes.  What a sequence keeps is declared once, in :data:`PLANES`: a
row a kind of thing a sequence can keep (:class:`Plane`), six today.
``cache.pool`` holds a dict with an entry for every plane the cache has,
always ``"kv"``, and the same donating programs, the one lock and the one
lifetime serve them all: ``alloc_seq`` gives a sequence what it needs of
each and ``free_seq`` (finish, cancel, preemption) takes it back.  A row
says the entry's shape, what the decode forward is handed for it, how a
step's new rows and a prompt's rows are written into it, what it tells the
pull's span, and why the block manifest cannot carry it; the cache, the
runner and the engine walk the rows and know no kind by name.  Which planes
a model has follows from what its module declares (:func:`kept_by`); a
plane a family does not name is no entry, no operand, and nothing in its
programs.  The kinds, in the table's order:

``"state"``: recurrent state (a state-space mixer's scan state and conv
tail, ``models/falcon_h1.py``), one leaf a kind of state, ``(state layers,
max_seqs + 1, *shape)``: a *row* a sequence slot and a last one for
staging, fixed-size and never paged.  A prompt's prefill leaves its state
in the staging row and ``scatter_prefill`` moves it to the sequence's row
in the program that scatters its K/V; the decode program steps the rows
``rows_of`` names.  The pool counts the layers that hold K/V and the store
those that hold state: two numbers where no layer holds both
(``models/lfm2.py``), each kind numbered among its own.

``"sel"``: a selector's cache, for attention that chooses the pages it
reads (``models/minicpm_sala.py``): for every ``stride`` positions of
every page of every K/V layer the sum of those positions' keys (a
*half-kernel*, ``ops/sparse_attention.py``), :func:`selector_shape`, paged
by the sequence's own table.  Only the cache's writers write it, a scatter
from the K it scatters and a step's write from the pool's rows again: so
writing a row twice changes nothing and no caller can write K and forget
its slot.

``"kvw"``: pages of a second kind, for layers that need only the last
``window`` positions (``models/afmoe.py``) and under one table would keep
what they never read again (4 of 5 layers at a 25k context): a second pool
over the window layers and ``max_seqs x (ceil(window / bs) + 1)`` blocks
(what the sequence slots can hold at once: it cannot run out), a second
free list and a second table a sequence, indexed like the other.  A column
wholly behind the window names no block (``window_blocks``, out of range)
and its block is back on the free list (``alloc_seq``, ``append_slot``);
the decode kernel starts its walk at the window's first column, and a
released block may go to another sequence at once, because every program
takes the pool donated and the device runs them in the order they were
enqueued.

``"latent"``: a latent page, for attention that caches ONE row a position
(``models/ling.py``: ``[c | k_rope]``), :func:`device_shape` with
``planes=1``, under the K/V pool's own table: the allocator knows nothing
of it.  A prompt's rows reach the scatter as its ``ks`` (``vs`` is not
read).  Such a family's ``"kv"`` pool has no layer and no byte, and stays
in the holder so that every program keeps its operands' labels.

``"index"``: an indexer's cache, for attention that picks its positions one
by one from scores of its own (``models/llama.py``, ``index_topk``;
``ops/indexed_attention.py``): ONE key of ``index_dim`` lanes a position a
layer, :func:`device_shape` with ``planes=1`` (its lanes padded to a whole
tile: 64 -> 128), under the K/V pool's own table, so the allocator knows
nothing of it.  It is a projection of its own and not a sum of K, as
``"sel"`` is, so no writer can make it from the pool: it is written from
the forward's own rows.  Those rows are *carried*: a position's index key
leaves every forward as one more head of its K (its first ``index_dim``
lanes; ``Plane.carried``), so that whoever writes a position's K, a step's
program, ``write_token`` or a prompt's scatter, handed device arrays or
numpy ones, writes its index key in the same act, and K's own heads reach
the pool without it (:func:`_carried_apart`).  A step reads it whole over a
row's context, and then the K/V rows of the positions it chose.

A table that shrinks.  A sequence whose model folds (``models/llama.py``,
``eva_window``; :func:`kept_by` reads ``folded_cache(cfg)``) keeps its open
window of ``fold_window`` positions exactly and every window that has closed
as ``fold_window / fold_chunk`` folded rows, each a K and a V of the pool's
own lanes.  So a folded row is a row of ``"kv"`` and no seventh pool, and the
folding is a property of that plane's TABLE: a sequence's table is ``[the
pages of a closed window's folded rows]* + [the open window's pages]``, and
the pool, its writers and the decode kernel see a plain table under a
shorter context.  Two lengths a sequence follow: the positions it has SEEN
(``_fill``; RoPE, the scheduler, the streams) and the rows it HOLDS
(:func:`held_rows` of that: the kernels' context, the scatter's count,
:meth:`PagedKVCache.blocks_needed`).  A window closes inside
:meth:`PagedKVCache.append_slot`, when the slot asked for is the first of
the next window: the fold is a program the runner hands the cache
(``PagedKVCache.folder``: it needs the model's parameters), which reads the
window's pages and writes the folded rows over the first of them, in the
order of the enqueues; the other pages go back to the free list at once and
the table's columns close up.  The pages of folded rows must be whole
(``fold_window / fold_chunk`` a multiple of the block size: refused at
set-up otherwise).  A folded page is immutable and could be shared or
exported; nothing does yet (:meth:`PagedKVCache.unshared`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from ray_tpu._private import rtlog
from ray_tpu._private.flight_recorder import _pid_alive
from ray_tpu._private.xla_watchdog import compile_budget

logger = rtlog.get("serve.llm.kv")


class NoFreeBlocks(Exception):
    """Allocation failed: the pool is exhausted (caller should preempt)."""


def reap_orphan_export_spools(base) -> List[str]:
    """Remove rtpu_llm_export_<pid>_* spool dirs whose owner is dead: a
    SIGKILLed prefill replica cannot remove its own tmpfs spool."""
    import shutil
    reaped = []
    if not base:
        return reaped
    try:
        names = os.listdir(base)
    except OSError:
        return reaped
    for name in names:
        if not name.startswith("rtpu_llm_export_"):
            continue
        try:
            pid = int(name.split("_")[3])
        except (IndexError, ValueError):
            continue
        if pid == os.getpid() or _pid_alive(pid):
            continue
        shutil.rmtree(os.path.join(base, name), ignore_errors=True)
        reaped.append(name)
    if reaped:
        logger.info("reaped %d orphaned export spool(s): %s",
                    len(reaped), reaped)
    return reaped


def device_shape(num_blocks: int, n_layer: int, block_size: int,
                 n_kv: int, head_dim: int, planes: int = 2) -> tuple:
    """The pool's shape on the device, ``(L, 2, N, bs, F)``: the one
    place that says it (the cache, the tests and whoever compiles a step
    program for shapes alone ask here).  ``F`` is a position's
    ``n_kv * head_dim`` features padded up to whole 128-lane tiles, the
    narrowest thing Mosaic copies out of HBM.  ``planes=1``: a latent
    pool, ``(L, 1, N, bs, F)``, one row a position (``n_kv`` 1,
    ``head_dim`` the row's features) where the other holds a K and a V."""
    f = n_kv * head_dim
    return (n_layer, planes, num_blocks, block_size, f + -f % 128)


def selector_shape(pool_shape: tuple, stride: int) -> tuple:
    """The selector's cache beside a pool of ``pool_shape``: ``(L, N, bs /
    stride, F)``, a half-kernel (``ops/sparse_attention.py``) for every
    ``stride`` positions of every page of every layer that holds K/V.  Its
    lanes are the pool's, because a half-kernel is a sum of the pool's K
    rows; the index plane's are not (a key of the indexer's own width, one
    a position and not one a ``stride``), so that plane's shape is
    :func:`device_shape`'s with one plane and says nothing of the pool."""
    n_layer, _, num_blocks, bs, f = pool_shape
    return (n_layer, num_blocks, bs // stride, f)


def window_columns(window: int, block_size: int) -> int:
    """The most table columns a window layer holds blocks for: the window's
    ``ceil(window / bs)`` and one more, for a window that starts inside a
    block."""
    return -(-window // block_size) + 1


def window_first_column(n_tokens: int, window: int, block_size: int) -> int:
    """The first table column a window layer still needs once ``n_tokens``
    positions are cached: the one that holds position ``n_tokens - window +
    1``, the first that the token at ``n_tokens`` sees."""
    return max(0, n_tokens - window + 1) // block_size


def held_rows(seen, window: int, chunk: int):
    """The rows a sequence that has seen ``seen`` positions HOLDS where a
    closed ``window`` is kept as a row a ``chunk`` (0: nothing folds): the
    folded rows of its closed windows and the open window's own.  Host
    ints, numpy arrays and traced arrays alike."""
    if not window:
        return seen
    return seen // window * (window // chunk) + seen % window


def held_rows_most(seen: int, window: int, chunk: int) -> int:
    """The most rows a sequence holds at once on its way to ``seen``
    positions: with the last window it fills still exact, or at its end."""
    if not window:
        return seen
    full, per = seen // window, window // chunk
    return max((full - 1) * per + window if full else 0,
               held_rows(seen, window, chunk))


def fold_reads(seen, own: bool, window: int, chunk: int, layers: int):
    """What queries that have ``seen`` positions each behind them (``own``:
    and their own, a chunk's; a decode step's new row is not in the pool)
    read under a folded cache, from the mathematics: the rows they hold,
    folded and exact, beside the positions a plain cache would hold; each
    summed over the queries and the layers."""
    seen = np.asarray(seen, np.int64)
    return dict(
        rows_read=int((held_rows(seen, window, chunk) + own).sum()) * layers,
        positions_seen=int((seen + own).sum()) * layers)


def _halves_rewritten(sel, pool, blocks, offsets):
    """The half-kernels that rows just written fall in, summed again from
    the pool: slot ``(blocks[r], offsets[r] // stride)`` of every layer
    becomes the sum of the K rows of its ``stride`` positions up to
    ``offsets[r]`` (what lies behind it in the page is an earlier owner's).
    From the pool and not from the row's K alone, so that writing a row a
    second time changes nothing, as for the K/V itself."""
    import jax.numpy as jnp
    n_layer, num_blocks, per_page, f = sel.shape
    bs = pool.shape[3]
    stride = bs // per_page
    first = offsets // stride * stride
    within = jnp.arange(stride)
    per_slab = num_blocks * bs
    at = jnp.minimum(blocks, num_blocks - 1) * bs + first         # (R,)
    rows = (2 * jnp.arange(n_layer)[:, None, None] * per_slab
            + at[None, :, None] + within)                        # (L, R, s)
    keys = pool.reshape(-1, f)[rows.reshape(-1)].reshape(*rows.shape, f)
    keep = (within[None, :] <= (offsets - first)[:, None])[None, :, :, None]
    halves = jnp.where(keep, keys, 0.0).sum(2)                    # (L, R, F)
    slot = blocks * per_page + offsets // stride
    slots = jnp.arange(n_layer)[:, None] * (num_blocks * per_page) + slot
    slots = jnp.where(blocks < num_blocks, slots,
                      n_layer * num_blocks * per_page)
    flat = sel.reshape(-1, f).at[slots.reshape(-1)].set(
        halves.reshape(-1, f).astype(sel.dtype), mode="drop")
    return flat.reshape(sel.shape)


def write_rows(pool, blocks, offsets, k, v=None):
    """``pool[:, 0 / 1, blocks[r], offsets[r]] = k / v[:, r]`` for every
    row ``r``, cast to the pool's type; traceable.  A pool of one plane (a
    latent pool) takes its rows as ``k`` and reads no ``v``.

    k, v: (L, R, KV, D), in head form as the models return them; they
    are laid flat along the lanes and zero-padded to the pool's ``F``
    here.  A row whose block is ``>= num_blocks`` writes nowhere: how a
    decode batch padded up to its bucket, and a prompt padded up to its
    bucket, keep their padding out of live blocks.

    One indexed update for a decode batch's few rows and a prompt's
    hundreds alike.  A row of one layer's K is ``F`` contiguous lanes,
    so the pool is taken as what it is in memory, ``L x 2 x N x bs`` rows
    of ``F``, and the ``L x 2 x R`` written ones are scattered into it by
    row number: the donated pool is updated in place and nothing else of
    it is passed over.  (Indexed as ``pool.at[:, :, blocks, offsets]``
    the TPU compiler copies the whole pool to a layout with the layers
    inside the tiles and back: the pass this format exists to remove.)"""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import lane_flat
    n_layer, planes, num_blocks, bs, f = pool.shape
    kv = jnp.stack([lane_flat(a, f) for a in (k, v)[:planes]],
                   axis=1)                                      # (L, 2, R, F)
    per_slab = num_blocks * bs              # rows of one layer's K (or V)
    row = blocks * bs + offsets                                 # (R,)
    rows = jnp.arange(planes * n_layer)[:, None] * per_slab \
        + row                                                   # (2 L, R)
    # a row sent out of range lies outside every slab, not in the next
    rows = jnp.where(blocks < num_blocks, rows,
                     planes * n_layer * per_slab)
    flat = pool.reshape(-1, f).at[rows.reshape(-1)].set(
        kv.reshape(-1, f).astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def _halves_of_prompt(sel, ks, table, n_tokens, pool):
    """A prompt's half-kernels from its K whole, ``ks`` (L, T, KV, D): a
    slot each ``stride`` positions, in the pages ``table`` names; the last
    may be part of one, which the decode steps' writes complete."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import lane_flat
    from ray_tpu.ops.sparse_attention import halves_of
    num_blocks, bs = pool.shape[2:4]
    per_page, f = sel.shape[2:]
    stride = bs // per_page
    halves = halves_of(lane_flat(ks, f), n_tokens, stride)
    first = jnp.arange(0, ks.shape[1], stride)
    slots = table[first // bs] * per_page + first % bs // stride
    slots = jnp.arange(sel.shape[0])[:, None] \
        * (num_blocks * per_page) + slots
    slots = jnp.where(first < n_tokens, slots, sel.size // f)
    return sel.reshape(-1, f).at[slots.reshape(-1)].set(
        halves.reshape(-1, f).astype(sel.dtype),
        mode="drop").reshape(sel.shape)


def _row_committed(store, row):
    """The state a prompt's prefill left in the staging row (the store's
    last) goes to the sequence's ``row``."""
    import jax
    from jax import lax
    return jax.tree.map(lambda s: lax.dynamic_update_index_in_dim(
        s, s[:, -1], row, 1), store)


def window_reads(ctx_lens, block_size: int, window: int, layers: int) -> dict:
    """What a decode step's window layers read, from its rows' context
    lengths: positions (the window's, or the context where it is shorter),
    blocks (the walk's columns, the first one whole), and the blocks full
    layers would have read in their place; each summed over the rows and
    the window layers."""
    lens = np.asarray(ctx_lens, np.int64)
    lo = np.maximum(lens - (window - 1), 0)
    held = -(-lens // block_size)
    return dict(
        window_positions=int((lens - lo).sum()) * layers,
        window_blocks=int((held - lo // block_size).sum()) * layers,
        window_blocks_unwindowed=int(held.sum()) * layers)


# ------------------------------------------------------------------ the planes
def _declared(mod, mcfg, name):
    """``mod.<name>(mcfg)``, or None for a module that does not export it."""
    return getattr(mod, name)(mcfg) if hasattr(mod, name) else None


@dataclasses.dataclass(frozen=True)
class Kept:
    """What one sequence of a model keeps on the device (:func:`kept_by`):
    the K/V pool's sizes and :class:`PagedKVCache`'s keywords, which say
    what each field is; the runner's plain reads."""

    n_kv: int
    head_dim: int
    kv_layers: int
    state: Optional[dict] = None
    state_layers: int = 0
    select_stride: int = 0
    select_block: int = 0            # the page the selection wants
    window_layers: int = 0
    window: int = 0
    latent_layers: int = 0
    latent_dim: int = 0
    index_layers: int = 0
    index_dim: int = 0
    index_topk: int = 0              # the positions a query attends to
    # a closed window of ``fold_window`` positions is kept as a row a
    # ``fold_chunk`` (0: every position keeps its row)
    fold_window: int = 0
    fold_chunk: int = 0
    # a prompt's prefill leaves its state in the holder: it runs through
    # the holder, donated
    staged: bool = False
    # a prompt's rows lie under two tables and reach the scatter as one
    # array a K and a V (:meth:`PagedKVCache.window_run`)
    packed: bool = False


def kept_by(mod, mcfg) -> Kept:
    """The one place that reads what a model's module declares of its
    cache: ``recurrent_state(cfg)`` (one sequence's state in one layer,
    name -> shape and type), ``cache_layers(cfg)`` (the layers of each
    kind: ``"kv"``, ``"state"`` and, where it has them, ``"window"`` /
    ``"latent"`` / ``"index"``), ``page_selector(cfg)`` (``{"stride",
    "block"}``) and ``folded_cache(cfg)`` (``{"window", "chunk"}``).  A
    module that declares none (GPT-2, Llama) keeps K/V in every layer; one
    with state and no count keeps both in every layer."""
    state = _declared(mod, mcfg, "recurrent_state")
    layers = _declared(mod, mcfg, "cache_layers") or {
        "kv": mcfg.n_layer, "state": mcfg.n_layer if state else 0}
    select = _declared(mod, mcfg, "page_selector") or {}
    window_layers = layers.get("window", 0)
    latent_layers = layers.get("latent", 0)
    index_layers = layers.get("index", 0)
    indexed = dict(index_layers=index_layers, index_dim=mcfg.index_dim,
                   index_topk=mcfg.index_topk) if index_layers else {}
    fold = _declared(mod, mcfg, "folded_cache")
    if fold:
        indexed.update(fold_window=fold["window"], fold_chunk=fold["chunk"])
    return Kept(
        getattr(mcfg, "n_kv_head", mcfg.n_head), mcfg.head_dim, layers["kv"],
        state, layers["state"], select.get("stride", 0),
        select.get("block", 0), window_layers,
        mcfg.sliding_window if window_layers else 0, latent_layers,
        mcfg.latent_row if latent_layers else 0, staged=bool(state),
        packed=bool(window_layers), **indexed)


@dataclasses.dataclass(frozen=True)
class Plane:
    """One kind of thing a sequence keeps: an entry of the holder's dict
    and all that the cache, the runner and the engine do with one.  A
    family with a new kind of page adds a row to :data:`PLANES` (and its
    kernel, and its module)."""

    name: str                        # the entry's key
    layers: str                      # :class:`Kept`'s count of its layers
    # (cache) -> the entry as ShapeDtypeStruct(s); None: this cache has none
    spec: Callable
    # the decode forward's keywords, the entry's and the operand's, and
    # (cache, block tables (bucket, MAXB), real rows) -> that operand, the
    # step's host operand for it
    handed: tuple = ()
    by_row: Optional[Callable] = None
    stepped: bool = False            # the forward returns it anew, first
    # a pool of rows, (L, planes, N, bs, F): the plane whose table names
    # its rows' blocks.  The pools share a step's or a prompt's rows in
    # this table's order, each its layers.  ``beside``: (cache, block) ->
    # a written token's block under a table of its own, which ``by_row``
    # hands a step and ``prompt`` (its columns, the first position) a
    # scatter
    table: str = ""
    beside: Optional[Callable] = None
    # (cache, sequence id or None: a warm-up, tokens) -> the scatter's
    # operands for it; ``committed`` (entry, that operand): its last act
    prompt: Optional[Callable] = None
    committed: Optional[Callable] = None
    # kept beside the K/V pool: (entry, K (L, T, KV, D), table, tokens,
    # pool), first in a scatter; (entry, pool, blocks, offsets), after a
    # step's rows
    from_prompt: Optional[Callable] = None
    rewritten: Optional[Callable] = None
    # (a step's real context lengths, cache) -> what ``llm.decode.pull`` is
    # told; (cache) -> its own counts a step (``held_counts``)
    reads: Optional[Callable] = None
    holds: Optional[Callable] = None
    # (staging, bucket, Kept) -> a chunked prompt's rows for the scatter
    staged: Optional[Callable] = None
    # a pool of one plane whose rows are carried: a position's row leaves
    # the forwards as one more head of its K, behind K's own, and is taken
    # off it by every writer (:func:`_carried_apart`).  ``chunk_reads``:
    # (a chunk's first position, its end, cache) -> what the chunk's span
    # is told, as ``reads`` tells a step's
    carried: bool = False
    chunk_reads: Optional[Callable] = None
    # why the block manifest cannot carry it / two sequences cannot share
    # it, behind ``{what}: {model}`` / ``a sequence with``; "": they can
    unexported: str = ""
    unshared: str = ""


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _nbytes(spec) -> int:
    import jax
    return sum(math.prod(s.shape) * np.dtype(s.dtype).itemsize
               for s in jax.tree.leaves(spec))


def _state_spec(c):
    # a row a sequence slot and one for staging, in each layer of state
    return {name: _sds((c.state_layers, c.state_rows + 1) + tuple(s.shape),
                       s.dtype) for name, s in c.state_spec.items()} \
        if c.state_spec else None


def _sel_spec(c):
    if c.select_stride and c.block_size % c.select_stride:
        raise ValueError(
            f"pages of {c.block_size} positions are no whole number "
            f"of the selector's {c.select_stride}")
    return _sds(selector_shape(c.kv_shape, c.select_stride), c.dtype) \
        if c.select_stride else None


def _window_spec(c):
    if c.window_layers and (not c.max_seqs or c.window < 1):
        raise ValueError(
            "window layers need max_seqs (their pool is sized by "
            "the sequence slots) and a window of positions")
    return _sds(device_shape(c.window_blocks, c.window_layers, c.block_size,
                             *c.block_shape[3:]), c.dtype) \
        if c.window_layers else None


def index_reads(contexts, topk: int, layers: int) -> dict:
    """What queries that see ``contexts`` positions each (their own the
    last) have to read under an index, from the mathematics whatever walks
    it: every position's index key, then the K/V of ``min(context, topk)``
    positions; each summed over the queries and the index layers."""
    seen = np.asarray(contexts, np.int64)
    return dict(positions_scored=int(seen.sum()) * layers,
                positions_read=int(np.minimum(seen, topk).sum()) * layers)


def _state_rows(c, tables, n):
    # whose table each is, the cache knows; padded rows name none
    return np.concatenate([c.rows_of(tables[:n]),
                           np.full(len(tables) - n, c.no_row, np.int32)])


PLANES = (
    Plane("kv", "kv_layers", lambda c: _sds(c.kv_shape, c.dtype), table="kv",
          staged=lambda staging, bucket, kept: tuple(
              staging[name][:, :bucket].reshape(
                  kept.kv_layers, bucket, kept.n_kv, kept.head_dim)
              for name in ("k", "v"))),
    Plane("state", "state_layers", _state_spec, handed=("state", "rows"),
          by_row=_state_rows,
          stepped=True, prompt=lambda c, seq_id, n: (c._committing(seq_id),),
          committed=_row_committed,
          unexported="keeps recurrent state beside its K/V blocks, and the "
          "manifest exports blocks only; nothing moves the state yet, so "
          "nothing is moved",
          unshared="recurrent state cannot be forked: its row is its own, "
          "and a shared first block would name two rows"),
    Plane("sel", "kv_layers", _sel_spec, handed=("selector",),
          from_prompt=_halves_of_prompt, rewritten=_halves_rewritten),
    Plane("kvw", "window_layers", _window_spec,
          handed=("window_pool", "window_tables"),
          by_row=lambda c, tables, n: c.window_tables(tables), table="kvw",
          beside=lambda c, block: c._window_block_beside(block),
          prompt=lambda c, seq_id, n: c._window_run(
              c.window_table(seq_id) if seq_id else [], n),
          reads=lambda lens, c: window_reads(lens, c.block_size, c.window,
                                             c.window_layers),
          holds=lambda c: c._window_holds(),
          unexported="keeps its window layers' K/V in a second pool under a "
          "second table, and the manifest exports the one table's blocks; "
          "nothing moves the window layers' yet, so nothing is moved",
          unshared="window layers cannot be forked: a shared window block "
          "would be given back by whichever holder's context passes it "
          "first (prefix sharing across window layers: ROADMAP R4)"),
    Plane("latent", "latent_layers", lambda c: _sds(device_shape(
              c.num_blocks, c.latent_layers, c.block_size, 1, c.latent_dim,
              planes=1), c.dtype) if c.latent_layers else None,
          handed=("latent_pool",), table="kv",
          # the pages the absorbed kernel walks
          reads=lambda lens, c: dict(latent_pages_read=int(
              (-(-np.asarray(lens, np.int64) // c.block_size)).sum())
              * c.latent_layers),
          holds=lambda c: dict(state_rows_held=c.state_rows_used(),
                               latent_blocks_held=c.used_block_count()),
          staged=lambda staging, bucket, kept: (
              staging["latent"][:, :bucket, None],) * 2,
          unexported="caches latent rows, and a block's wire format is a K "
          "and a V a layer; nothing exports a latent page yet, so nothing "
          "is moved",
          unshared="latent pages cannot be forked: nothing shares a prefix "
          "across latent layers yet (ROADMAP, Reach)"),
    Plane("index", "index_layers", lambda c: _sds(device_shape(
              c.num_blocks, c.index_layers, c.block_size, 1, c.index_dim,
              planes=1), c.dtype) if c.index_layers else None,
          handed=("index_pool",), carried=True,
          # a step's query sees its row's cached positions and its own
          reads=lambda lens, c: index_reads(
              np.asarray(lens, np.int64) + 1, c.index_topk, c.index_layers),
          chunk_reads=lambda first, end, c: index_reads(
              np.arange(first, end, dtype=np.int64) + 1, c.index_topk,
              c.index_layers),
          holds=lambda c: dict(index_blocks_held=c.used_block_count()),
          unexported="keeps an index key a position beside its K/V, and a "
          "block's wire format is a K and a V a layer; nothing exports an "
          "index page yet, so nothing is moved",
          unshared="an index plane cannot be forked: nothing shares a prefix "
          "across index pages yet (ROADMAP, Reach)"),
)


def _carried_apart(held, k):
    """(K's own heads, {plane: its carried rows (L, R, 1, lanes)}): ``k``
    (L, R, heads, D) holds, behind K's own heads, one head for every plane
    of ``held`` whose rows are carried, in the table's order; a head's
    first lanes, as many as the plane's pool has or the head is wide, are
    the row."""
    carried = [p for p in planes_of(held) if p.carried]
    if not carried:
        return k, {}
    own = k.shape[2] - len(carried)
    return k[:, :, :own], {
        p.name: k[:, :, own + i:own + i + 1, :held[p.name].shape[-1]]
        for i, p in enumerate(carried)}


def staged_rows(kept: Kept, staging, bucket: int) -> tuple:
    """A chunked prompt's (ks, vs) for :meth:`PagedKVCache.scatter_prefill`
    out of its chunks' ``staging``, where its rows lie under one table: cut
    by the pool that has layers."""
    import jax.numpy as jnp
    plane = next(p for p in PLANES if p.staged and getattr(kept, p.layers))
    # a staging of a model that folds is laid out as the rows held
    bucket = held_rows_most(bucket, kept.fold_window, kept.fold_chunk)
    ks, vs = plane.staged(staging, bucket, kept)
    for plane in PLANES:
        if plane.carried and getattr(kept, plane.layers):
            # its staged rows, a head wide, ride behind K's heads
            ks = jnp.concatenate([ks, staging[plane.name][
                :, :bucket, None, :kept.head_dim].astype(ks.dtype)], axis=2)
    return ks, vs


def planes_of(held) -> tuple:
    """The rows of :data:`PLANES` whose entries ``held`` has, in the table's
    order (``held``: a holder's dict, or anything that holds their names)."""
    return tuple(p for p in PLANES if p.name in held)


def handed_to_forward(held, by_row) -> tuple:
    """(the decode forward's keywords for what the holder holds beside its
    K/V pool, the tables a step's new rows go through): ``by_row`` are the
    step's host operands, in the table's order as ``decode`` built them."""
    by_row, keywords, tables = list(by_row), {}, {}
    for plane in planes_of(held):
        operand = by_row.pop(0) if plane.by_row else None
        keywords.update(zip(plane.handed, (held[plane.name], operand)))
        if plane.beside:
            tables[plane.name] = operand
    assert not by_row, f"{len(by_row)} operands no plane of {set(held)} takes"
    return keywords, tables


def stepped_by_forward(held, results: list) -> dict:
    """The holder's dict with what the decode forward stepped (the first of
    ``results``, its results behind K/V: they are taken off it)."""
    return {**held, **{plane.name: results.pop(0)
                       for plane in planes_of(held) if plane.stepped}}


def slots_reserved(held, tables, ctx_lens, n_real) -> tuple:
    """(blocks by table, offsets) of a decode step's new rows: the slot
    ``append_slot`` reserved for each, ``(table[ctx // bs], ctx % bs)``
    through every table of ``tables`` (name -> (B, MAXB)).  Rows padded up
    to the bucket are sent out of range: they write nowhere."""
    import jax.numpy as jnp
    bs = held["kv"].shape[3]
    rows = jnp.arange(ctx_lens.shape[0])
    blocks = {name: jnp.where(rows < n_real, table[rows, ctx_lens // bs],
                              held[name].shape[2])
              for name, table in tables.items()}
    return blocks, ctx_lens % bs


def block_slots_reserved(held, table, ctx_lens, commit, span: int) -> tuple:
    """(blocks by table, offsets) of a block pass's new rows, ``span`` a
    row, row-major: position ``ctx + j`` of row ``r`` goes to the slot
    ``append_block`` reserved for it, ``(table[r, (ctx + j) // bs], (ctx +
    j) % bs)``.  ``commit`` (R,) bool: the rows whose pass writes; every
    other (a denoise pass, a row padded up to the bucket) is sent out of
    range and writes nowhere."""
    import jax.numpy as jnp
    num_blocks, bs = held["kv"].shape[2:4]
    at = ctx_lens[:, None] + jnp.arange(span)                   # (R, span)
    rows = jnp.arange(ctx_lens.shape[0])[:, None]
    blocks = jnp.where(commit[:, None], table[rows, at // bs], num_blocks)
    return {"kv": blocks.reshape(-1)}, (at % bs).reshape(-1)


def rows_written(held, blocks, offsets, k, v) -> dict:
    """New rows into every plane that takes them, for a decode step and for
    ``write_token`` alike: k, v (L, R, KV, D) hold the pools' rows one pool
    behind another in the table's order (the full layers', then the window
    layers'; a latent pool's are ``k``), each goes to its pool at the blocks
    its table names (``blocks``: table -> (R,)) and ``offsets``; then what
    is kept beside the K/V pool is brought up to date from it, so whoever
    writes K writes that too.  Carried rows (``Plane.carried``) are taken
    off K first and go to their own pools at the K/V table's blocks.
    Returns the holder's dict."""
    at = 0
    k, carried = _carried_apart(held, k)
    for name, rows in carried.items():
        held = {**held, name: write_rows(held[name], blocks["kv"], offsets,
                                         rows)}
    for plane in planes_of(held):
        layers = held[plane.name].shape[0] if plane.table else 0
        if layers:
            mine = [a if layers == a.shape[0] else a[at:at + layers]
                    for a in (k, v)]
            held = {**held, plane.name: write_rows(
                held[plane.name], blocks[plane.table], offsets, *mine)}
            at += layers
    for plane in planes_of(held):
        if plane.rewritten:
            held = {**held, plane.name: plane.rewritten(
                held[plane.name], held["kv"], blocks["kv"], offsets)}
    return held


@functools.cache
def _programs() -> SimpleNamespace:
    """The pool's jitted programs, built on first use (importing this
    module must not import jax: drivers import it).  The three writers
    take the pool donated and return ``(pool, None)``, what
    :meth:`DevicePool.donate` expects; block ids, offsets, tables and
    lengths are traced, so a program is built once per shape of K/V,
    never per value."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops.paged_attention import heads_apart, lane_flat

    def _write_rows(held, blocks, offsets, k, v, *beside):
        # ``beside``: the blocks under each table beside the sequence's own
        own = [p.name for p in planes_of(held) if p.beside]
        return rows_written(held, {"kv": blocks, **dict(zip(own, beside))},
                            offsets, k, v), None

    def _scatter_prefill(held, table, ks, vs, n_tokens, *prompt):
        # token t of the padded prompt -> slot t % bs of block table[t // bs];
        # padding (t >= n_tokens) is sent out of range and dropped.  ks / vs
        # (L, T, KV, D); where a pool lies under a table of its own they are
        # (1, rows, KV, D): each pool's layers one behind another, the pools
        # in the table's order, the second's a run of positions ``first ..``
        # (whole blocks: its table's columns).  ``prompt``: the planes'
        # operands, in the table's order
        planes, prompt = planes_of(held), list(prompt)
        ks, carried = _carried_apart(held, ks)
        mine = {p.name: [prompt.pop(0) for _ in range(1 + bool(p.beside))]
                for p in planes if p.prompt}
        num_blocks, bs = held["kv"].shape[2:4]
        pools = [p for p in planes if p.table and held[p.name].shape[0]]
        runs = {p.name: mine[p.name][0].shape[0] * bs
                for p in pools if p.beside}
        shared = sum(held[p.name].shape[0] for p in pools
                     if p.name not in runs)
        positions = (ks.shape[1] - sum(
            held[name].shape[0] * run for name, run in runs.items())) \
            // shared if runs else ks.shape[1]
        heads, at = ks.shape[2:], 0
        with jax.named_scope("kv_write"):
            t = jnp.arange(positions)
            blocks = jnp.where(t < n_tokens, table[t // bs], num_blocks)
            for plane in planes:
                if plane.from_prompt:
                    held = {**held, plane.name: plane.from_prompt(
                        held[plane.name], ks, table, n_tokens, held["kv"])}
            for plane in pools:
                pool, rows = held[plane.name], (ks, vs)
                if plane.name in runs:
                    columns, first = mine[plane.name]
                    t = jnp.arange(runs[plane.name])
                    blocks = jnp.where(first + t < n_tokens,
                                       columns[t // bs], pool.shape[2])
                offsets = t % bs
                if runs:
                    n = pool.shape[0] * t.shape[0]
                    rows = [a[0, at:at + n].reshape(pool.shape[0], -1, *heads)
                            for a in rows]
                    at += n
                held = {**held, plane.name: write_rows(pool, blocks, offsets,
                                                       *rows)}
            for name, rows in carried.items():
                # under the K/V pool's own table, the prompt's positions
                t = jnp.arange(positions)
                held = {**held, name: write_rows(
                    held[name], jnp.where(t < n_tokens, table[t // bs],
                                          num_blocks), t % bs, rows)}
        for plane in planes:
            if plane.committed:
                held = {**held, plane.name: plane.committed(
                    held[plane.name], *mine[plane.name])}
        return held, None

    # a block between its wire format (L, 2, bs, KV, D) and the pool's
    # pool[:, :, i], (L, 2, bs, F): the lane padding never crosses
    def _load_block(held, block_id, block):
        pool = held["kv"]
        flat = lane_flat(block, pool.shape[-1]).astype(pool.dtype)
        return {**held, "kv": lax.dynamic_update_index_in_dim(
            pool, flat, block_id, 2)}, None

    def _read_block(held, block_id, heads):
        return heads_apart(lax.dynamic_index_in_dim(
            held["kv"], block_id, 2, keepdims=False), *heads)

    def _read_blocks(held, heads, name="kv"):
        return heads_apart(jnp.moveaxis(held[name], 2, 0), *heads)

    # the names are rows of lock_watchdog.DONATED (jaxlint pins them)
    kv_write_rows = jax.jit(_write_rows, donate_argnums=(0,))
    kv_scatter_prefill = jax.jit(_scatter_prefill, donate_argnums=(0,))
    kv_load_block = jax.jit(_load_block, donate_argnums=(0,))
    return SimpleNamespace(
        write_rows=kv_write_rows, scatter_prefill=kv_scatter_prefill,
        load_block=kv_load_block,
        read_block=jax.jit(_read_block, static_argnames="heads"),
        read_blocks=jax.jit(_read_blocks, static_argnames=("heads", "name")))


class DevicePool:
    """What a sequence's planes hold on the device, whoever holds it now:
    a dict, ``{"kv": the block pool's array}`` and beside it an entry for
    every other plane the cache has (:data:`PLANES`), built from
    ``described``: name -> ``ShapeDtypeStruct`` (or a tree of them).

    A donating program deletes the array it was given and returns a new
    one over the same memory, so nobody may keep the array itself:
    callers keep this holder (``cache.pool``) and every program runs
    through :meth:`donate` or :meth:`read`, which take and rebind the
    array under one lock.  That lock is what lets ``attach`` load blocks
    from its caller's thread while the engine's loop decodes: it is held
    for the enqueue only, and the device runs the programs in the order
    they were enqueued."""

    def __init__(self, described: dict):
        self.described = described
        self.planes = planes_of(described)
        self._pool_lock = threading.Lock()
        self._array = None                             # guarded by: _pool_lock
        self.nbytes = _nbytes(described)    # on the device, every plane
        self.fill(0)

    def abstract(self):
        """What the holder holds, as shapes (for whoever lowers a program
        that takes it, without the array)."""
        import jax
        return jax.tree.map(lambda s: s, self.described)

    def donate(self, program, *args):
        """Run ``program(array, *args) -> (array, result)``, which donates
        the array; keep the array it returns and hand back the result."""
        with self._pool_lock:
            self._array, result = program(self._array, *args)
        return result

    def read(self, program, *args, **static):
        """``program(array, *args, **static)``, for a program that only
        reads."""
        with self._pool_lock:
            return program(self._array, *args, **static)

    def __getitem__(self, index):
        """Of the K/V pool's array, in its device format: how a model's
        decode step handed the holder itself (eagerly, outside the
        runner's programs) reads a layer."""
        return self.read(lambda held: held["kv"][index])

    def fill(self, value) -> None:
        """A new array of ``value``.  The old one is waited for and
        deleted first: an array that a program still has to write or read
        keeps its memory past its deletion, the new one is allocated at
        the enqueue, and two pools may not fit the device (seen on the
        v5e: 2.52 GB in use after a fill of a 1.26 GB pool)."""
        import jax
        import jax.numpy as jnp
        with self._pool_lock:
            for old in jax.tree.leaves(self._array):
                old.block_until_ready().delete()
            self._array = jax.tree.map(
                lambda s: jnp.full(s.shape, value, s.dtype), self.described)


class PagedKVCache:
    """Block pool + tables + refcounts for one engine instance."""

    @classmethod
    def for_engine(cls, cfg, kept: Kept, dtype=np.float32) -> "PagedKVCache":
        """The cache of an engine of ``cfg`` (an ``EngineConfig``) whose
        model keeps ``kept``: a row of state a sequence slot beside the
        blocks, the pool laid out for the layers that hold K/V."""
        if kept.select_block and kept.select_block != cfg.block_size:
            raise ValueError(
                f"{cfg.model} selects pages of {kept.select_block} "
                f"positions: block_size {cfg.block_size} is not its page")
        planes = {name: value for name, value in vars(kept).items()
                  if name not in ("n_kv", "head_dim", "kv_layers",
                                  "select_block", "staged", "packed")}
        return cls(cfg.num_blocks, kept.kv_layers, cfg.block_size, kept.n_kv,
                   kept.head_dim, dtype, max_seqs=cfg.max_num_seqs, **planes)

    def __init__(self, num_blocks: int, n_layer: int, block_size: int,
                 n_kv: int, head_dim: int, dtype=np.float32, *,
                 state=None, max_seqs: int = 0, state_layers=None,
                 select_stride: int = 0, window_layers: int = 0,
                 window: int = 0, latent_layers: int = 0,
                 latent_dim: int = 0, index_layers: int = 0,
                 index_dim: int = 0, index_topk: int = 0,
                 fold_window: int = 0, fold_chunk: int = 0):
        """``n_layer``: the layers that hold K/V.  The keywords are
        :class:`Kept`'s fields (:meth:`for_engine` hands them all; a test
        names a plane with its own).  ``state``: one sequence's recurrent
        state in one layer, name -> ``ShapeDtypeStruct``, in ``max_seqs``
        rows and one for staging, in each of ``state_layers`` layers (None:
        as many as hold K/V).  ``select_stride``: the positions a
        half-kernel pools.  ``window_layers``, ``window``: the layers that
        hold the last ``window`` positions only, in a pool of ``max_seqs``
        x :func:`window_columns` blocks.  ``latent_layers``,
        ``latent_dim``: the layers that cache one row of ``latent_dim``
        features a position; such a family's ``n_layer`` is 0.
        ``index_layers``, ``index_dim``, ``index_topk``: the layers that
        cache an index key of ``index_dim`` lanes a position beside their
        K/V (all of them), and the positions a query then attends to.
        ``fold_window``, ``fold_chunk``: a closed window of so many
        positions is kept as one row a chunk (a table that shrinks)."""
        if fold_window and (
                fold_chunk < 1 or fold_window % fold_chunk
                or (fold_window // fold_chunk) % block_size
                or state or window_layers or select_stride or latent_layers
                or index_layers):
            raise ValueError(
                f"a window of {fold_window} positions folded {fold_chunk} "
                f"to 1 is {fold_window / max(fold_chunk, 1):g} rows: no "
                f"whole number of pages of {block_size}, or beside a plane "
                "the fold is not written for (K/V under one table alone)")
        if index_layers and (index_layers != n_layer or window_layers
                             or select_stride or latent_layers
                             or not 0 < index_dim <= head_dim):
            raise ValueError(
                "an index plane lies under the K/V pool's one table, a key "
                "a position of every K/V layer, carried as a head of K: "
                f"{index_layers} index layers of {index_dim} lanes beside "
                f"{n_layer} K/V layers with heads of {head_dim}")
        if latent_layers and (n_layer or window_layers or select_stride):
            raise ValueError(
                "latent pages beside K/V pages in one model are not "
                "written: a prompt's rows reach the scatter as its K")
        self.num_blocks = num_blocks
        self.block_shape = (n_layer, 2, block_size, n_kv, head_dim)
        self.block_size = block_size
        self.dtype = np.dtype(dtype)
        self.block_nbytes = int(np.prod(self.block_shape)) * \
            self.dtype.itemsize
        self.max_seqs, self.state_spec = max_seqs, state
        # rows of recurrent state a sequence can be given, the staging
        # row (the store's last) and the store's bytes; 0 without state
        self.state_rows = max_seqs if state else 0
        self.staging_row = self.state_rows
        # names no row: a decode step reads somewhere and writes nowhere
        self.no_row = self.state_rows + 1
        self.kv_layers = n_layer
        self.state_layers = (n_layer if state_layers is None
                             else state_layers) if state else 0
        self.select_stride = select_stride
        self.window_layers, self.window = window_layers, window
        self.window_blocks = max_seqs * window_columns(window, block_size) \
            if window_layers else 0
        self.latent_layers, self.latent_dim = latent_layers, latent_dim
        self.index_layers, self.index_dim = index_layers, index_dim
        self.index_topk = index_topk
        self.fold_window, self.fold_chunk = fold_window, fold_chunk
        # (the window's pages) -> None: the fold of one closed window, the
        # runner's program (it needs the model's parameters)
        self.folder: Optional[Callable] = None
        self.kv_shape = device_shape(num_blocks, n_layer, block_size, n_kv,
                                     head_dim)
        # every plane this cache has, as shapes, and the planes' bytes
        described = {p.name: p.spec(self) for p in PLANES}
        self.pool = DevicePool({name: spec for name, spec in described.items()
                                if spec is not None})
        self.planes = self.pool.planes
        self.state_bytes, self.select_bytes, self.window_bytes, \
            self.latent_bytes, self.index_bytes = (
                _nbytes(described[name]) for name in (
                    "state", "sel", "kvw", "latent", "index"))
        # what the device format costs in memory beside the wire format's
        # bytes: the lanes that pad F (LLMEngine.stats()["kv_lane_pad_bytes"])
        self.lane_pad_bytes = _nbytes(described["kv"]) \
            - num_blocks * self.block_nbytes
        # one step region for the writers below (DESIGN.md §4q): a
        # scatter program per prefill bucket, write_token, load_block
        self._write_budget = compile_budget("llm.kv_write")
        self._lock = threading.Lock()
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))  # guarded by: _lock
        self._tables: Dict[str, List[int]] = {}                      # guarded by: _lock
        self._fill: Dict[str, int] = {}                              # guarded by: _lock
        self._ref: Dict[int, int] = {}                               # guarded by: _lock
        # a sequence's row of recurrent state, the rows nobody has, and
        # who owns a table's first block (how rows_of knows a table)
        self._rows: Dict[str, int] = {}                              # guarded by: _lock
        self._free_rows: List[int] = list(range(self.state_rows - 1, -1, -1))  # guarded by: _lock
        self._owner: Dict[int, str] = {}                             # guarded by: _lock
        self.state_commits = 0                                       # guarded by: _lock
        # pages of two kinds: the window pool's free blocks, a sequence's
        # window table (indexed like its table; ``window_blocks``, out of
        # range, where the block went back) and the blocks given back
        self._wfree: List[int] = list(range(self.window_blocks - 1, -1, -1))  # guarded by: _lock
        self._wtables: Dict[str, List[int]] = {}                     # guarded by: _lock
        # its first column that still names a block
        self._wfirst: Dict[str, int] = {}                            # guarded by: _lock
        self.window_released = 0                                     # guarded by: _lock
        self._released_told = 0       # of those, told to held_counts()
        # a table that shrinks: a sequence's closed windows that are folded
        # (its table's first columns), and the windows folded so far
        self._folded: Dict[str, int] = {}                            # guarded by: _lock
        self.windows_folded = 0                                      # guarded by: _lock
        self._folded_told = 0         # of those, told to held_counts()
        # bytes of pool data that crossed between host and device, either
        # way: K/V given as numpy, blocks exported or imported
        self.host_bytes = 0                                          # guarded by: _lock

    # ------------------------------------------------------------ allocation
    def held_rows(self, n_tokens):
        """The rows a sequence of ``n_tokens`` positions holds
        (:func:`held_rows` at this cache's fold; ``n_tokens`` itself where
        nothing folds)."""
        return held_rows(n_tokens, self.fold_window, self.fold_chunk)

    def blocks_needed(self, n_tokens: int) -> int:
        return max(1, -(-self.held_rows(n_tokens) // self.block_size))

    def free_block_count(self) -> int:
        with self._lock:
            return len(self._free)

    def used_block_count(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)

    def alloc_seq(self, seq_id: str, n_tokens: int) -> List[int]:
        """Allocate blocks for ``n_tokens`` of context; table starts full
        to ``n_tokens`` (prefill scatters into them immediately).  With
        recurrent state the sequence gets its row as well, or nothing."""
        n = self.blocks_needed(n_tokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already allocated")
            if len(self._free) < n:
                raise NoFreeBlocks(
                    f"need {n} blocks, {len(self._free)} free")
            first = 0
            if self.window_layers:
                # the columns the first decode step can see, and no other
                first = window_first_column(n_tokens, self.window,
                                            self.block_size)
                if len(self._wfree) < n - first:
                    raise NoFreeBlocks(
                        f"need {n - first} window blocks, "
                        f"{len(self._wfree)} free: more sequences hold "
                        "blocks than the slots the window pool is sized for")
            if self.state_rows:
                if not self._free_rows:
                    raise NoFreeBlocks(
                        f"all {self.state_rows} rows of recurrent state "
                        "are taken")
                self._rows[seq_id] = self._free_rows.pop()
            blocks = [self._free.pop() for _ in range(n)]
            for b in blocks:
                self._ref[b] = 1
            self._tables[seq_id] = blocks
            self._fill[seq_id] = n_tokens
            if self.fold_window:
                # a prompt's closed windows reach the scatter folded
                self._folded[seq_id] = n_tokens // self.fold_window
            if self.state_rows or self.window_layers:
                self._owner[blocks[0]] = seq_id
            if self.window_layers:
                self._wfirst[seq_id] = first
                self._wtables[seq_id] = [self.window_blocks] * first + [
                    self._wfree.pop() for _ in range(n - first)]
        return blocks

    def append_slot(self, seq_id: str) -> tuple:
        """Reserve the next token slot for ``seq_id``.

        Returns (block_id, offset_in_block, grew); grows the table by
        one block at a block boundary (``grew`` True).  Raises
        NoFreeBlocks under cache pressure — the scheduler's preemption
        trigger.  A reservation whose decode step then fails must be
        returned with :meth:`rollback_slot` or every later slot is off
        by one.

        Under a table that shrinks the slot is that of the next row HELD,
        and a window the sequence has filled is folded first
        (:meth:`_close_windows`)."""
        if self.fold_window:
            self._close_windows(seq_id)
        with self._lock:
            fill = self._fill[seq_id]
            table = self._tables[seq_id]
            blk_i, off = divmod(self.held_rows(fill), self.block_size)
            grew = False
            if blk_i == len(table):
                if not self._free:
                    raise NoFreeBlocks(f"pool exhausted growing {seq_id!r}")
                b = self._free.pop()
                self._ref[b] = 1
                table.append(b)
                grew = True
            if self.window_layers:
                # the token at ``fill`` sees ``fill - window + 1 ..``: the
                # columns wholly behind that go back first, then the
                # window table grows with the other
                wtable = self._wtables[seq_id]
                first = window_first_column(fill, self.window,
                                            self.block_size)
                for j in range(self._wfirst[seq_id], first):
                    self._wfree.append(wtable[j])
                    wtable[j] = self.window_blocks
                    self.window_released += 1
                self._wfirst[seq_id] = max(first, self._wfirst[seq_id])
                if grew:
                    wtable.append(self._wfree.pop())
            self._fill[seq_id] = fill + 1
            return table[blk_i], off, grew

    def _close_windows(self, seq_id: str) -> None:
        """Fold every window ``seq_id`` has filled and still holds exactly
        (one, when its next slot is the first of the next window): the
        folder reads the window's pages and writes the folded rows over the
        first of them, the rest go back to the free list at once and the
        table's columns close up.  The program is enqueued behind the step
        that wrote the window's last row and before any that reads the
        table as it is after."""
        window, bs = self.fold_window, self.block_size
        keep, pages = window // self.fold_chunk // bs, window // bs
        while True:
            with self._lock:
                done = self._folded[seq_id]
                if self._fill[seq_id] // window <= done:
                    return
                at = done * keep
                closing = self._tables[seq_id][at:at + pages]
            if self.folder is None:
                raise RuntimeError(
                    f"sequence {seq_id!r} has filled a window of {window} "
                    "positions and the cache was handed no folder (the "
                    "runner's program: ModelRunner.cache = this cache)")
            self.folder(np.asarray(closing, np.int32))
            with self._lock:
                del self._tables[seq_id][at + keep:at + pages]
                for b in closing[keep:]:
                    self._given_back(b)
                self._folded[seq_id] = done + 1
                self.windows_folded += 1

    def _given_back(self, b: int) -> bool:
        """One holder less of block ``b`` (``_lock`` held); True where it
        went back to the free list."""
        self._ref[b] -= 1
        if self._ref[b]:
            return False
        del self._ref[b]
        self._free.append(b)
        return True

    def rollback_slot(self, seq_id: str, grew: bool) -> None:
        """Undo one :meth:`append_slot` reservation (failed decode step)."""
        with self._lock:
            if seq_id not in self._fill:
                return                     # freed/preempted meanwhile
            self._fill[seq_id] -= 1
            if grew and self.window_layers:
                self._wfree.append(self._wtables[seq_id].pop())
            if grew:
                self._given_back(self._tables[seq_id].pop())

    def append_block(self, seq_id: str, n: int) -> List[bool]:
        """Reserve the next ``n`` token slots for ``seq_id`` at once (a
        block of positions, written together by its commit pass): all of
        them or, under cache pressure, none (``NoFreeBlocks``).  Returns
        each slot's ``grew``, what :meth:`rollback_block` takes."""
        grew: List[bool] = []
        try:
            for _ in range(n):
                grew.append(self.append_slot(seq_id)[2])
        except NoFreeBlocks:
            self.rollback_block(seq_id, grew)
            raise
        return grew

    def rollback_block(self, seq_id: str, grew: List[bool]) -> None:
        """Undo one :meth:`append_block` (a block whose passes were thrown
        away: a failed step, a stream cut inside it)."""
        for one in reversed(grew):
            self.rollback_slot(seq_id, one)

    def free_seq(self, seq_id: str) -> int:
        """Release a sequence's blocks (refcounted); returns #freed."""
        with self._lock:
            blocks = self._tables.pop(seq_id, None)
            self._fill.pop(seq_id, None)
            row = self._rows.pop(seq_id, None)
            if row is not None:
                self._free_rows.append(row)
            if blocks:
                self._owner.pop(blocks[0], None)
            self._wfree.extend(b for b in self._wtables.pop(seq_id, ())
                               if b != self.window_blocks)
            self._wfirst.pop(seq_id, None)
            self._folded.pop(seq_id, None)
            if not blocks:
                return 0
            return sum(self._given_back(b) for b in blocks)

    def fork_seq(self, seq_id: str, new_seq_id: str) -> None:
        """Share a sequence's blocks with a new id (refcount bump) —
        the prefix-sharing/export primitive."""
        for why in self.unshared():
            raise NotImplementedError(f"a sequence with {why}")
        with self._lock:
            blocks = list(self._tables[seq_id])
            for b in blocks:
                self._ref[b] += 1
            self._tables[new_seq_id] = blocks
            self._fill[new_seq_id] = self._fill[seq_id]

    def unexported(self) -> List[str]:
        """Why the block manifest cannot carry a sequence of this cache
        (behind ``{what}: {model}``): a reason a plane that has one, and
        the table's own where it shrinks; empty: it can."""
        folds = ["keeps its closed windows folded, and a manifest names a "
                 "block a run of positions; nothing exports a folded page "
                 "yet, so nothing is moved"] if self.fold_window else []
        return [p.unexported for p in self.planes if p.unexported] + folds

    def unshared(self) -> List[str]:
        """Why two sequences cannot share blocks of this cache (behind ``a
        sequence with``); empty: they can."""
        folds = ["a table that shrinks cannot be forked: a folded page is "
                 "immutable and could be shared, but the open window's "
                 "pages are folded and given back by whoever closes it "
                 "first (ROADMAP, Reach)"] if self.fold_window else []
        return [p.unshared for p in self.planes if p.unshared] + folds

    # ------------------------------------------------------------- accessors
    def table(self, seq_id: str) -> List[int]:
        with self._lock:
            return list(self._tables[seq_id])

    def fill(self, seq_id: str) -> int:
        with self._lock:
            return self._fill[seq_id]

    def has_seq(self, seq_id: str) -> bool:
        with self._lock:
            return seq_id in self._tables

    def seq_ids(self) -> List[str]:
        with self._lock:
            return list(self._tables)

    def window_table(self, seq_id: str) -> List[int]:
        """The sequence's window table, indexed like :meth:`table`; a
        column whose block went back holds ``window_blocks``."""
        with self._lock:
            return list(self._wtables[seq_id])

    def window_tables(self, block_tables: np.ndarray) -> np.ndarray:
        """The window table behind each block table (B, MAXB), at the same
        width: that of the sequence which owns the table's first block.  A
        table no sequence owns (a row padded up to the bucket, a warm-up
        call) gets zeros: block 0, read under a context of no length."""
        out = np.zeros(block_tables.shape, np.int32)
        with self._lock:
            for i, t in enumerate(block_tables):
                wtable = self._wtables.get(self._owner.get(int(t[0])))
                if wtable is not None:
                    out[i, :len(wtable)] = wtable[:out.shape[1]]
        return out

    def window_counts(self) -> tuple:
        """(window blocks held now, given back so far, what the sequences
        alive now would hold if the window layers kept one table with the
        others: a block a column of every table)."""
        with self._lock:
            return (self.window_blocks - len(self._wfree),
                    self.window_released,
                    sum(len(t) for t in self._wtables.values()))

    def _window_holds(self) -> dict:
        held, released, unwindowed = self.window_counts()
        told, self._released_told = self._released_told, released
        return dict(window_blocks_held=held,
                    window_blocks_held_unwindowed=unwindowed,
                    window_blocks_released=released - told)

    def _fold_holds(self) -> dict:
        """Blocks the live sequences hold under the table that shrinks,
        what they would hold with every position's row kept, and the
        windows folded since last asked."""
        if not self.fold_window:
            return {}
        with self._lock:
            unfolded = sum(-(-n // self.block_size)
                           for n in self._fill.values())
            held = self.num_blocks - len(self._free)
            told, self._folded_told = self._folded_told, self.windows_folded
            return dict(fold_blocks_held=held, fold_blocks_unfolded=unfolded,
                        windows_folded=self.windows_folded - told)

    def held_counts(self) -> dict:
        """The planes' own counts at a step, for ``metrics_catalog.
        tell_step``: window blocks held and what one table for all layers
        would hold (counters: each step adds what the live sequences hold
        now, so their ratio is the mean over steps), given back since last
        asked; rows and latent blocks held; under a table that shrinks the
        blocks held, what an unfolded table would hold, windows folded."""
        return {**{name: n for plane in self.planes if plane.holds
                   for name, n in plane.holds(self).items()},
                **self._fold_holds()}

    def window_pool_blocks(self) -> np.ndarray:
        """Every block of the window layers' pool in the wire format of
        :meth:`blocks`, ``(window_blocks, window layers, 2, bs, KV, D)``,
        copied from the device (the tests)."""
        return np.asarray(self.pool.read(
            _programs().read_blocks, heads=self.block_shape[3:], name="kvw"))

    def half_kernels(self) -> np.ndarray:
        """The selector's cache, ``(L, N, halves a page, F)``, copied from
        the device (the tests)."""
        return np.asarray(self.pool.read(lambda held: held["sel"]))

    def state_rows_used(self) -> int:
        with self._lock:
            return len(self._rows)

    def state_row(self, seq_id: str) -> int:
        with self._lock:
            return self._rows[seq_id]

    def rows_of(self, block_tables: np.ndarray) -> np.ndarray:
        """The row of recurrent state behind each block table (B, MAXB):
        that of the sequence which owns the table's first block.  A
        table no sequence owns gets ``no_row``, outside the store."""
        with self._lock:
            return np.asarray(
                [self._rows.get(self._owner.get(int(t[0])), self.no_row)
                 for t in block_tables], np.int32)

    # ------------------------------------------------------- block transfer
    def _crossed(self, nbytes: int) -> None:
        with self._lock:
            self.host_bytes += nbytes

    def _write(self, program, *args, host=()) -> None:
        """One donating write; ``host`` are the arguments that cross the
        host link when they are numpy."""
        self._crossed(sum(a.nbytes for a in host
                          if isinstance(a, np.ndarray)))
        with self._write_budget:
            self.pool.donate(program, *args)

    def block_bytes(self, block_id: int) -> bytes:
        """One block's contiguous bytes in its wire format (the data-plane
        export unit), gathered out of the pool and copied from the
        device."""
        self._wire_holds_k_and_v("block_bytes")
        block = self.pool.read(_programs().read_block, np.int32(block_id),
                               heads=self.block_shape[3:])
        self._crossed(self.block_nbytes)
        return np.asarray(block).tobytes()

    def _wire_holds_k_and_v(self, what: str) -> None:
        if self.latent_layers:
            raise NotImplementedError(
                f"{what}: a block's wire format is a K and a V a layer, and "
                "this cache's pages are latent rows; nothing exports a "
                "latent page yet")

    def index_keys(self) -> np.ndarray:
        """Every page of the index plane, ``(num_blocks, index layers, bs,
        index_dim)`` without the lane padding, copied from the device (the
        tests)."""
        return np.asarray(self.pool.read(
            lambda held: held["index"][:, 0, ..., :self.index_dim]
        )).swapaxes(0, 1)

    def latent_blocks(self) -> np.ndarray:
        """Every latent page, ``(num_blocks, latent layers, bs,
        latent_dim)`` without the lane padding, copied from the device
        (the tests)."""
        return np.asarray(self.pool.read(
            lambda held: held["latent"][:, 0, ..., :self.latent_dim]
        )).swapaxes(0, 1)

    def blocks(self) -> np.ndarray:
        """Every block in its wire format, ``(num_blocks,) + block_shape``,
        copied from the device: for a caller that checks what the pool
        holds without knowing how the device holds it (the tests)."""
        return np.asarray(self.pool.read(_programs().read_blocks,
                                         heads=self.block_shape[3:]))

    def load_block(self, block_id: int, raw) -> None:
        """Copy one imported block's bytes (wire format) to the device,
        into its block."""
        self._wire_holds_k_and_v("load_block")
        block = np.frombuffer(raw, dtype=self.dtype).reshape(self.block_shape)
        self._write(_programs().load_block, np.int32(block_id), block,
                    host=(block,))

    def scatter_prefill(self, seq_id: str, ks, vs, n_tokens: int) -> None:
        """Write prefill KV (L, T_pad, KV, D), numpy or device arrays of
        any float type, into the seq's blocks (only the first
        ``n_tokens`` positions are real, and only they are written).
        With recurrent state, the same program commits the state the
        prompt's prefill staged to the sequence's row."""
        # under a table that shrinks ks / vs are the rows HELD after the
        # prompt, and so is the count the program writes
        self._write(_programs().scatter_prefill, *self._scatter_args(
            self.table(seq_id), ks, vs, self.held_rows(n_tokens),
            *self._prompt_operands(seq_id, n_tokens)), host=(ks, vs))

    def _prompt_operands(self, seq_id, n_tokens: int) -> list:
        """The scatter program's operands behind the prompt's length, in
        the planes' order (``seq_id`` None: a warm-up's)."""
        return [a for plane in self.planes if plane.prompt
                for a in plane.prompt(self, seq_id, n_tokens)]

    def _committing(self, seq_id) -> int:
        """The row a scatter commits the staged state to (a warm-up: the
        staging row, onto itself)."""
        if seq_id is None:
            return self.staging_row
        with self._lock:
            self.state_commits += 1
            return self._rows[seq_id]

    def window_run(self, n_tokens: int) -> tuple:
        """(first position, positions) of the run of a prompt of
        ``n_tokens`` that its window layers' blocks hold: whole blocks from
        :func:`window_first_column` on, :func:`window_columns` of them.
        What a prefill hands :meth:`scatter_prefill` of those layers, behind
        the full layers' K/V of the padded prompt, all along axis 1 of a
        ``(1, rows, KV, D)`` array (the runner packs it,
        ``prefill_result``)."""
        first = window_first_column(n_tokens, self.window, self.block_size)
        return (first * self.block_size,
                window_columns(self.window, self.block_size)
                * self.block_size)

    def _window_run(self, wtable: List[int], n_tokens: int) -> tuple:
        """The scatter program's two operands for the window layers: the
        window table's columns of the run, and its first position."""
        first, positions = self.window_run(n_tokens)
        columns = np.full(positions // self.block_size, self.window_blocks,
                          np.int32)
        have = wtable[first // self.block_size:][:len(columns)]
        columns[:len(have)] = have
        return columns, np.int32(first)

    def warm_scatter(self, ks, vs) -> None:
        """Build the scatter program for this shape of K/V without
        writing a token (the runner calls it with a bucket's first
        prefill, so the bucket's two programs are built together)."""
        from ray_tpu.util import tracing
        args = self._scatter_args([], ks, vs, 0,
                                  *self._prompt_operands(None, 0))
        tracing.register_program(
            f"llm.prefill.scatter.{ks.shape[1]}", _programs().scatter_prefill,
            (self.pool.abstract(), *tracing.abstract(args)))
        self._write(_programs().scatter_prefill, *args, host=(ks, vs))

    def _scatter_args(self, table: List[int], ks, vs, n_tokens: int,
                      *row: int) -> tuple:
        # the table at the width of the padded prompt; the blocks past
        # the sequence's own are out of range, and dropped on the device
        rows = ks.shape[1]
        if self.window_layers:
            # behind the full layers' K/V lie the window layers' runs
            rows = (rows - self.window_layers * self.window_run(0)[1]) \
                // self.kv_layers
        padded = np.full(-(-rows // self.block_size), self.num_blocks,
                         np.int32)
        padded[:len(table)] = table[:len(padded)]
        return (padded, ks, vs, np.int32(n_tokens),
                *(np.int32(r) if np.ndim(r) == 0 else r for r in row))

    def write_token(self, block_id: int, offset: int, k, v) -> None:
        """Write one token's (L, KV, D) K/V into its slot.  The decode
        step writes its own (``ModelRunner.decode``); this is for a
        caller that holds K/V from elsewhere, and rewriting what a step
        wrote changes nothing."""
        beside = [plane.beside(self, block_id) for plane in self.planes
                  if plane.beside]
        self._write(_programs().write_rows, np.int32([block_id]),
                    np.int32([offset]), k[:, None], v[:, None], *beside,
                    host=(k, v))

    def _window_block_beside(self, block_id: int) -> np.ndarray:
        """The window block of the same column of whoever holds
        ``block_id`` (a written token's k / v hold the full layers' rows
        first)."""
        with self._lock:
            return np.int32([next(
                (self._wtables[sid][t.index(block_id)]
                 for sid, t in self._tables.items() if block_id in t),
                self.window_blocks)])

    # -------------------------------------------------------------- teardown
    def close(self) -> None:
        """Drop the pool: its device memory goes with the last program
        that uses it (idempotent)."""
        self.pool = None
