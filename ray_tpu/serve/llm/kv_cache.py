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

Recurrent state.  A model family whose sequences hold more than K/V (a
state-space mixer's scan state and conv tail: ``models/falcon_h1.py``)
describes one sequence's state per layer, and the cache is built with
that description (``state=``).  What ``cache.pool`` holds is always a
dict, ``{"kv": the array}``, and for such a family ``{"kv": the array,
"state": the store}``, the
store one leaf per kind of state, ``(n_layer, max_seqs + 1, *shape)``: a
*row* per sequence slot and a last one for staging.  The same holder, the
same donating programs, one lifetime: ``alloc_seq`` gives a sequence its
row with its blocks and ``free_seq`` (finish, cancel, preemption) takes
both back.  The row is fixed-size and never paged.  A prompt's prefill
program leaves its state in the staging row (``model_runner.py``), and
``scatter_prefill`` moves it to the sequence's row in the program that
scatters its K/V; the decode program steps the rows ``rows_of`` names
for the tables it was given.  Without ``state=`` the dict has no
``"state"`` and the programs no operand for it.

Layers that differ in kind.  The pool's ``n_layer`` counts the layers that
hold K/V and the store's leading axis the layers that hold state
(``state_layers=``): the same number where every layer holds both
(Falcon-H1), two numbers where some layers attend and the others keep a
conv's tail and none does both (``models/lfm2.py``: the module counts them
in ``cache_layers(cfg)``).  Each kind is numbered among its own in layer
order; a pool laid out for all the layers of such a model would be mostly
empty and the decode kernel's layer index wrong.  Nothing else here knows:
a row is a row and a block a block.

A selector's cache.  A family whose attention chooses the pages it reads
(``models/minicpm_sala.py``: block-sparse attention over compressed keys)
holds a third thing, which, unlike a row of state, grows with the context:
for every ``stride`` positions of every page of every layer that holds
K/V, the sum of those positions' keys (a *half-kernel*:
``ops/sparse_attention.py`` pools a compressed key from two neighbouring
ones, so no pooled key straddles a page in storage even where its window
does).  The cache is built with the stride (``select_stride=``, from the
module's ``page_selector(cfg)``) and the holder then holds ``{"kv", "state",
"sel"}``, ``sel`` :func:`selector_shape` = ``(n_layer, num_blocks,
block_size / stride, F)``: paged by the sequence's own block table, so a
page's slots come and go with the page (``alloc_seq``, ``free_seq``,
preemption) and cost no allocator of their own.  It is written by the
cache's own writers and by nobody else: ``scatter_prefill`` computes a
prompt's slots from the K it scatters, and ``write_rows`` (the decode
step's write, ``write_token``) sums the slot a new row falls in again
from the pool's rows, so writing a row twice changes nothing and no
caller can write K and forget its slot.  (``load_block`` writes none: the
one family with a selector keeps state rows too, and the engine refuses to
attach a sequence of such a model.)  A family that names no selector has
no ``"sel"`` in its holder, and its programs none of this.

Pages of two kinds.  A family whose attention is full in some layers and a
sliding window in the others (``models/afmoe.py``; its ``cache_layers``
counts a third kind, ``"window"``) needs every position of a context in
the one kind of layer and the last ``window`` in the other.  Held in one
pool under one table, the window layers would keep what they never read
again: 4 of 5 layers at a 25k context.  So the holder has a second pool,
``"kvw"``, :func:`device_shape` over the window layers and over
``max_seqs x (ceil(window / bs) + 1)`` blocks (what the sequence slots can
hold at once: it cannot run out, and the scheduler is told nothing of
it), and the manager a second free list and a second table a sequence,
under the one lock, with the one lifetime.  The window table is indexed
like the other (column ``j`` is positions ``j bs ..``), so both grow in
one :meth:`append_slot` and one ``grew`` undoes both; a column wholly
behind the window names no block (``window_blocks``, out of range) and its
block is back on the window free list: given back in ``alloc_seq`` (a
prompt's window blocks are only those its first decode step can see) and
in every ``append_slot`` that carries the window past a block's last
position.  The decode kernel starts its walk at the window's first column
(``ops/paged_attention.py``), so a released column is never read.  A
released block may be handed to another sequence at once: every program
that reads or writes the pool takes it donated, the device runs them in
the order they were enqueued, and the step that last read the block was
enqueued before any write of its next owner.  ``free_seq`` (finish,
cancel, preemption) returns both tables' blocks; ``rollback_slot`` undoes
a growth in both and leaves what was released released (it lies behind the
window of the position reserved again); ``fork_seq`` refuses, because a
shared window block would be given back by whichever holder passes it
first.  ``window_held`` / ``window_released`` / ``window_unwindowed`` count
the window blocks held now, given back so far, and what one table for all
layers would hold for the sequences alive now.  A family that names no
window has no ``"kvw"`` in its holder, no second list, and none of this in
its programs.

A latent page.  A family whose attention caches ONE row a position and not
a K and a V (``models/ling.py``: multi-head latent attention, the row ``[c
| k_rope]`` of ``kv_lora_rank + qk_rope_head_dim`` features; its
``cache_layers`` counts a kind ``"latent"``) gets a third kind of page: a
pool of ONE plane, ``"latent"``, :func:`device_shape` with ``planes=1`` =
``(latent layers, 1, num_blocks, block_size, F)``, the row's features
zero-padded to whole lanes (576 -> 640).  It has the other pool's
``num_blocks`` and lies under the same table: block ``i`` of the one is
block ``i`` of the other, so ``alloc_seq``, ``append_slot``,
``rollback_slot``, ``free_seq`` and preemption know nothing of it, and it
costs no list, no lock and no lifetime of its own.  It is written by the
cache's own writers: :func:`write_rows` takes a pool of either form (``v``
None where there is one plane), ``scatter_prefill`` lays a prompt's rows
(handed as its ``ks``, ``(latent layers, T, 1, R)``; ``vs`` is not read)
and ``write_token`` one token's.  The absorbed decode kernel
(``ops/paged_attention.latent_attention_decode``) reads a page once for
keys and values.  Such a family's layers hold no K/V today: its ``"kv"``
pool has no layer and no byte, and stays in the holder so that every
program keeps its operands' labels.  A latent page is not exported
(``block_bytes`` / ``load_block`` refuse: the wire format is K and V) and
not shared (``fork_seq`` refuses); the one family that has them keeps
state rows too.  A family that names no latent layer has no ``"latent"``
in its holder and none of this in its programs.
"""

from __future__ import annotations

import functools
import math
import os
import threading
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

from ray_tpu._private import rtlog
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


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


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
    ``stride`` positions of every page of every layer that holds K/V."""
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


def write_rows(pool, blocks, offsets, k, v=None, sel=None):
    """``pool[:, 0 / 1, blocks[r], offsets[r]] = k / v[:, r]`` for every
    row ``r``, cast to the pool's type; traceable.  A pool of one plane (a
    latent pool) takes its rows as ``k`` and no ``v``.  With ``sel`` (the
    selector's cache of a family that has one) the half-kernels those rows
    fall in are brought up to date from the K just written, and the result
    is ``(pool, sel)``: whoever writes K writes them.

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
    pool = flat.reshape(pool.shape)
    if sel is None:
        return pool
    return pool, _halves_rewritten(sel, pool, blocks, offsets)


def write_rows_by_kind(held, blocks, wblocks, offsets, k, v):
    """:func:`write_rows` where pages are of two kinds: k, v (L, R, KV, D)
    hold the full layers' rows first and then the window layers'; the one
    go to ``held["kv"]`` at ``blocks``, the other to ``held["kvw"]`` at
    ``wblocks``, both at ``offsets``.  Returns the holder's dict."""
    n_full = held["kv"].shape[0]
    return {**held,
            "kv": write_rows(held["kv"], blocks, offsets, k[:n_full],
                             v[:n_full]),
            "kvw": write_rows(held["kvw"], wblocks, offsets, k[n_full:],
                              v[n_full:])}


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
    from ray_tpu.ops.sparse_attention import halves_of

    def _write_rows(held, blocks, offsets, k, v, *wblocks):
        # the half-kernels the rows fall in too, where a selector's cache
        # is kept
        if wblocks:
            return write_rows_by_kind(held, blocks, wblocks[0], offsets, k,
                                      v), None
        if "latent" in held:
            return {**held, "latent": write_rows(held["latent"], blocks,
                                                 offsets, k)}, None
        sel = held.get("sel")
        if sel is None:
            return {**held, "kv": write_rows(held["kv"], blocks, offsets,
                                             k, v)}, None
        pool, sel = write_rows(held["kv"], blocks, offsets, k, v, sel)
        return {**held, "kv": pool, "sel": sel}, None

    def _scatter_prefill(held, table, ks, vs, n_tokens, *row):
        # token t of the padded prompt -> slot t % bs of block table[t // bs];
        # padding (t >= n_tokens) is sent out of range and dropped
        pool = held["kv"]
        num_blocks, bs = pool.shape[2:4]
        if "kvw" in held:
            # pages of two kinds: ks / vs are (1, rows, KV, D), the full
            # layers' K/V of the padded prompt one behind another and then
            # each window layer's run of positions ``first ..`` (whole
            # blocks, the window's columns); ``wtable``: those columns
            wtable, first = row
            wpool = held["kvw"]
            run = wtable.shape[0] * bs
            n_full, n_win = pool.shape[0], wpool.shape[0]
            heads = ks.shape[2:]
            split = ks.shape[1] - n_win * run
            with jax.named_scope("kv_write"):
                t = jnp.arange(split // n_full)
                blocks = jnp.where(t < n_tokens, table[t // bs], num_blocks)
                pool = write_rows(
                    pool, blocks, t % bs,
                    ks[0, :split].reshape(n_full, -1, *heads),
                    vs[0, :split].reshape(n_full, -1, *heads))
                t = jnp.arange(run)
                blocks = jnp.where(first + t < n_tokens, wtable[t // bs],
                                   wpool.shape[2])
                wpool = write_rows(
                    wpool, blocks, t % bs,
                    ks[0, split:].reshape(n_win, run, *heads),
                    vs[0, split:].reshape(n_win, run, *heads))
            return {**held, "kv": pool, "kvw": wpool}, None
        with jax.named_scope("kv_write"):
            t = jnp.arange(ks.shape[1])
            blocks = jnp.where(t < n_tokens, table[t // bs], num_blocks)
            sel = held.get("sel")
            if sel is not None:
                # a prompt's half-kernels from its K whole, a slot each
                # ``stride`` positions; the last may be part of one, which
                # the decode steps' writes complete
                per_page, f = sel.shape[2:]
                stride = bs // per_page
                halves = halves_of(lane_flat(ks, f), n_tokens, stride)
                first = jnp.arange(0, ks.shape[1], stride)
                slots = table[first // bs] * per_page + first % bs // stride
                slots = jnp.arange(sel.shape[0])[:, None] \
                    * (num_blocks * per_page) + slots
                slots = jnp.where(first < n_tokens, slots, sel.size // f)
                held = {**held, "sel": sel.reshape(-1, f).at[
                    slots.reshape(-1)].set(
                        halves.reshape(-1, f).astype(sel.dtype),
                        mode="drop").reshape(sel.shape)}
            if "latent" in held:
                # a latent page: the prompt's rows are ``ks``, and the
                # family's layers hold no K/V
                held = {**held, "latent": write_rows(held["latent"], blocks,
                                                     t % bs, ks)}
            else:
                held = {**held,
                        "kv": write_rows(pool, blocks, t % bs, ks, vs)}
        if row:
            # recurrent state: the prompt's, which its prefill left in the
            # staging row, goes to the sequence's row
            held = {**held, "state": jax.tree.map(
                lambda s: lax.dynamic_update_index_in_dim(
                    s, s[:, -1], row[0], 1), held["state"])}
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
    """The block pool's device array, whoever holds it now, as ``{"kv":
    that array}``; with ``state`` (a store's leaves as
    ``ShapeDtypeStruct``s) the store beside it, ``"state"``, with ``sel``
    the selector's cache, ``"sel"``, and with ``window`` the window
    layers' pool, ``"kvw"``, and with ``latent`` the latent layers' pool
    of one plane, ``"latent"``.

    A donating program deletes the array it was given and returns a new
    one over the same memory, so nobody may keep the array itself:
    callers keep this holder (``cache.pool``) and every program runs
    through :meth:`donate` or :meth:`read`, which take and rebind the
    array under one lock.  That lock is what lets ``attach`` load blocks
    from its caller's thread while the engine's loop decodes: it is held
    for the enqueue only, and the device runs the programs in the order
    they were enqueued."""

    def __init__(self, shape, dtype, state=None, sel=None, window=None,
                 latent=None):
        self.shape, self.dtype = tuple(shape), dtype
        self.state = state
        # the latent layers' pool of one plane (a ShapeDtypeStruct), for a
        # family that caches a latent row a position: ``"latent"``
        self.latent = latent
        # the window layers' pool (a ShapeDtypeStruct), for a family whose
        # layers hold pages of two kinds: one more entry, ``"kvw"``
        self.window = window
        # the selector's cache (a ShapeDtypeStruct), for a family that
        # chooses its pages: one more entry of what is held, ``"sel"``
        self.sel = sel
        self._pool_lock = threading.Lock()
        self._array = None                             # guarded by: _pool_lock
        self.fill(0)

    def abstract(self):
        """What the holder holds, as shapes (for whoever lowers a program
        that takes it, without the array)."""
        import jax
        kv = jax.ShapeDtypeStruct(self.shape, self.dtype)
        return self._held(kv, lambda s: s)

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
            self._array = self._held(
                jnp.full(self.shape, value, self.dtype),
                lambda s: jnp.full(s.shape, value, s.dtype))

    def _held(self, kv, made):
        """What the holder holds around ``kv``: beside it the store and
        the selector's cache of a family that has them, each leaf ``made``
        from its description."""
        import jax
        held = {"kv": kv}
        if self.state is not None:
            held["state"] = jax.tree.map(made, self.state)
        if self.sel is not None:
            held["sel"] = made(self.sel)
        if self.window is not None:
            held["kvw"] = made(self.window)
        if self.latent is not None:
            held["latent"] = made(self.latent)
        return held


class PagedKVCache:
    """Block pool + tables + refcounts for one engine instance."""

    def __init__(self, num_blocks: int, n_layer: int, block_size: int,
                 n_kv: int, head_dim: int, dtype=np.float32, *,
                 state=None, max_seqs: int = 0, state_layers=None,
                 select_stride: int = 0, window_layers: int = 0,
                 window: int = 0, latent_layers: int = 0,
                 latent_dim: int = 0):
        """``n_layer``: the layers that hold K/V.  ``state``: one
        sequence's recurrent state in one layer, name ->
        ``ShapeDtypeStruct`` (a model module's ``recurrent_state``), for a
        family that has one; the store then has ``max_seqs`` rows and one
        for staging, in each of ``state_layers`` layers (None: as many as
        hold K/V).  ``select_stride``: the positions a half-kernel of the
        selector's cache pools (a model module's ``page_selector``), for a
        family whose attention chooses its pages; 0: none is kept.
        ``window_layers`` and ``window``: the layers that hold the last
        ``window`` positions only (a model module's ``cache_layers`` and
        sliding window), beside the ``n_layer`` that hold every position:
        a second pool of ``max_seqs`` x :func:`window_columns` blocks, which
        the sequence slots cannot exhaust.  ``latent_layers`` and
        ``latent_dim``: the layers that cache one latent row of
        ``latent_dim`` features a position (a model module's
        ``cache_layers`` and latent row), in a pool of one plane under the
        same table; such a family's ``n_layer`` is 0."""
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
        # rows of recurrent state a sequence can be given, the staging
        # row (the store's last) and the store's bytes; 0 without state
        self.state_rows = max_seqs if state else 0
        self.staging_row = self.state_rows
        # names no row: a decode step reads somewhere and writes nowhere
        self.no_row = self.state_rows + 1
        store = None
        self.kv_layers = n_layer
        self.state_layers = 0
        if state:
            import jax
            self.state_layers = n_layer if state_layers is None \
                else state_layers
            store = {name: jax.ShapeDtypeStruct(
                (self.state_layers, max_seqs + 1) + tuple(s.shape), s.dtype)
                for name, s in state.items()}
        self.state_bytes = sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in (store or {}).values())
        shape = device_shape(num_blocks, n_layer, block_size, n_kv, head_dim)
        # what the device format costs in memory beside the wire format's
        # bytes: the lanes that pad F (LLMEngine.stats()["kv_lane_pad_bytes"])
        self.lane_pad_bytes = int(np.prod(shape)) * self.dtype.itemsize \
            - num_blocks * self.block_nbytes
        sel = None
        if select_stride:
            import jax
            if block_size % select_stride:
                raise ValueError(
                    f"pages of {block_size} positions are no whole number "
                    f"of the selector's {select_stride}")
            sel = jax.ShapeDtypeStruct(selector_shape(shape, select_stride),
                                       self.dtype)
        self.select_bytes = int(np.prod(sel.shape)) * self.dtype.itemsize \
            if sel is not None else 0
        self.window_layers, self.window = window_layers, window
        self.window_blocks = 0
        wpool = None
        if window_layers:
            import jax
            if not max_seqs or window < 1:
                raise ValueError(
                    "window layers need max_seqs (their pool is sized by "
                    "the sequence slots) and a window of positions")
            self.window_blocks = max_seqs * window_columns(window,
                                                           block_size)
            wpool = jax.ShapeDtypeStruct(
                device_shape(self.window_blocks, window_layers, block_size,
                             n_kv, head_dim), self.dtype)
        self.window_bytes = math.prod(wpool.shape) * self.dtype.itemsize \
            if wpool is not None else 0
        self.latent_layers, self.latent_dim = latent_layers, latent_dim
        lpool = None
        if latent_layers:
            import jax
            lpool = jax.ShapeDtypeStruct(
                device_shape(num_blocks, latent_layers, block_size, 1,
                             latent_dim, planes=1), self.dtype)
        self.latent_bytes = math.prod(lpool.shape) * self.dtype.itemsize \
            if lpool is not None else 0
        self.pool = DevicePool(shape, self.dtype, state=store, sel=sel,
                               window=wpool, latent=lpool)
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
        # bytes of pool data that crossed between host and device, either
        # way: K/V given as numpy, blocks exported or imported
        self.host_bytes = 0                                          # guarded by: _lock

    # ------------------------------------------------------------ allocation
    def blocks_needed(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.block_size))

    def free_block_count(self) -> int:
        with self._lock:
            return len(self._free)

    def used_block_count(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)

    def can_alloc(self, n_blocks: int) -> bool:
        with self._lock:
            return len(self._free) >= n_blocks

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
        by one."""
        with self._lock:
            fill = self._fill[seq_id]
            table = self._tables[seq_id]
            blk_i, off = divmod(fill, self.block_size)
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

    def rollback_slot(self, seq_id: str, grew: bool) -> None:
        """Undo one :meth:`append_slot` reservation (failed decode step)."""
        with self._lock:
            if seq_id not in self._fill:
                return                     # freed/preempted meanwhile
            self._fill[seq_id] -= 1
            if grew and self.window_layers:
                self._wfree.append(self._wtables[seq_id].pop())
            if grew:
                b = self._tables[seq_id].pop()
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    del self._ref[b]
                    self._free.append(b)

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
            if not blocks:
                return 0
            freed = 0
            for b in blocks:
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    del self._ref[b]
                    self._free.append(b)
                    freed += 1
            return freed

    def fork_seq(self, seq_id: str, new_seq_id: str) -> None:
        """Share a sequence's blocks with a new id (refcount bump) —
        the prefix-sharing/export primitive."""
        if self.state_rows:
            raise NotImplementedError(
                "a sequence with recurrent state cannot be forked: its row "
                "is its own, and a shared first block would name two rows")
        if self.window_layers:
            raise NotImplementedError(
                "a sequence with window layers cannot be forked: a shared "
                "window block would be given back by whichever holder's "
                "context passes it first (prefix sharing across window "
                "layers: ROADMAP R4)")
        if self.latent_layers:
            raise NotImplementedError(
                "a sequence with latent pages cannot be forked: nothing "
                "shares a prefix across latent layers yet (ROADMAP, Reach)")
        with self._lock:
            blocks = list(self._tables[seq_id])
            for b in blocks:
                self._ref[b] += 1
            self._tables[new_seq_id] = blocks
            self._fill[new_seq_id] = self._fill[seq_id]

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
        row = ()
        if self.state_rows:
            with self._lock:
                row = (self._rows[seq_id],)
                self.state_commits += 1
        if self.window_layers:
            row = self._window_run(self.window_table(seq_id), n_tokens)
        self._scatter(self.table(seq_id), ks, vs, n_tokens, *row)

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
        row = (self.staging_row,) if self.state_rows else ()
        if self.window_layers:
            row = self._window_run([], 0)
        args = self._scatter_args([], ks, vs, 0, *row)
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

    def _scatter(self, table: List[int], ks, vs, n_tokens: int,
                 *row: int) -> None:
        self._write(_programs().scatter_prefill,
                    *self._scatter_args(table, ks, vs, n_tokens, *row),
                    host=(ks, vs))

    def write_token(self, block_id: int, offset: int, k, v) -> None:
        """Write one token's (L, KV, D) K/V into its slot.  The decode
        step writes its own (``ModelRunner.decode``); this is for a
        caller that holds K/V from elsewhere, and rewriting what a step
        wrote changes nothing."""
        wblock = ()
        if self.window_layers:
            # the window block of the same column of whoever holds
            # ``block_id``; k / v hold the full layers' rows first
            with self._lock:
                wblock = (np.int32([next(
                    (self._wtables[sid][t.index(block_id)]
                     for sid, t in self._tables.items() if block_id in t),
                    self.window_blocks)]),)
        self._write(_programs().write_rows, np.int32([block_id]),
                    np.int32([offset]), k[:, None], v[:, None], *wblock,
                    host=(k, v))

    # -------------------------------------------------------------- teardown
    def close(self) -> None:
        """Drop the pool: its device memory goes with the last program
        that uses it (idempotent)."""
        self.pool = None
