"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

Reference contrast (SURVEY.md §2.4): Ray core has no MoE/expert parallelism —
"EP" in its ecosystem is user code (DeepSpeed-MoE) inside Train worker actors,
with NCCL all-to-alls the framework never sees.  Here EP is a first-class op:
expert weights carry a leading ``num_experts`` axis sharded
``P("expert", ...)``, token dispatch/combine are einsums against one-hot
dispatch tensors, and GSPMD lowers the resulting resharding to all-to-alls
over ICI.  No shard_map needed — the op stays in automatic-sharding land so
it composes with dp/fsdp/tp on the same mesh.

Design follows the GShard/Switch dispatch formulation (public): top-k gating
with an auxiliary load-balancing loss, fixed expert capacity with token
dropping, einsum-based dispatch/combine (MXU-friendly — the dispatch tensors
are the only non-matmul cost and XLA fuses their construction).

``dropless_moe_ffn`` is the other formulation (OLMoE, MegaBlocks): no
capacity and no dropped token; the assignments are sorted by expert and
the experts run as grouped matmuls over ragged groups.  At 8 of 64
experts a token the one-hot dispatch above costs 5-7 times the experts'
own matmuls; the sort costs a few passes over the rows: the gather into
expert order and, back, a gather and a sum over a token's k slots, each
the other's backward.  The router's weight goes into the experts with the
sorted rows and multiplies the hidden rows (``down(w h) = w down(h)``), so
the way back carries no weight, writes no float32 copy of the rows and
needs none of them for its gradient.

One sort makes the permutation, and what the layer needs beside it is read
off that sort in a form the compiler can use without a pass of its own
(``_sort_by_expert``, ``_sorted_assignments``).  The sort of (expert id,
iota, weight) by the id returns the order and the weights in that order at
once; the order sorted back against an iota is its inverse, and the
weights' gradient sorted back by the order is the gradient.  Both indices
are outputs of sorts over an iota, so they are permutations of the N k
assignments by construction, whatever the routing: every gather of the
layer promises its indices (``_take_rows``) and XLA neither fills nor
selects over the gathered rows.  The assignments are numbered slot by
slot, so the k slots are the leading axis of the rows gathered back and
the sum over them re-tiles nothing.  The counts are a compare of the ids
with the E experts, summed; nothing of the layer is a scatter.  A layer
that holds a share of the experts sorts its own experts' rows to the
front and carries to the experts and back only those: two Pallas kernels
whose work ends at ``held_rows`` (``spread_held_rows``, ``sum_held_slots``).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array       # load-balance loss (scalar)
    router_z_loss: jax.Array  # logit magnitude regularizer (scalar)
    fraction_dropped: jax.Array


def expert_capacity(num_tokens: int, num_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots; multiple of 8 for TPU-friendly tiling."""
    cap = int(math.ceil(k * num_tokens * capacity_factor / num_experts))
    return max(8, -(-cap // 8) * 8)


def topk_router(x: jax.Array, w_router: jax.Array, k: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token → expert assignment.

    x: (N, d) tokens; w_router: (d, E).  Returns (gates (N,E) with zeros off
    the top-k, logits (N,E), topk_idx (N,k)).  float32 softmax for stability
    regardless of activation dtype.
    """
    logits = jnp.asarray(x, jnp.float32) @ jnp.asarray(w_router, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_vals, topk_idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs)
    gates = jnp.put_along_axis(gates, topk_idx, topk_vals, axis=-1,
                               inplace=False)
    # renormalize the kept mass so combine weights sum to 1 per token
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, logits, topk_idx


def _dispatch_tensors(gates: jax.Array, capacity: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build (dispatch (N,E,C) bool, combine (N,E,C) float, dropped (N,))
    from gate weights.  Position within an expert is assignment order
    (cumsum over tokens); tokens past capacity are dropped.
    """
    N, E = gates.shape
    assigned = gates > 0.0                                   # (N, E)
    # position of each token in each expert's queue (0-based)
    pos = jnp.cumsum(assigned.astype(jnp.int32), axis=0) - 1  # (N, E)
    keep = assigned & (pos < capacity)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, -1), capacity,
                            dtype=gates.dtype)               # (N, E, C)
    dispatch = pos_oh
    combine = pos_oh * gates[..., None]
    dropped = assigned.any(-1) & ~keep.any(-1)
    return dispatch, combine, dropped


def load_balance_loss(gates: jax.Array, logits: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Switch-style aux loss: E * <fraction_tokens_e> · <mean_prob_e>, plus
    router z-loss penalizing logit magnitude."""
    E = gates.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = (gates > 0).astype(jnp.float32).mean(0)    # (E,)
    mean_prob = probs.mean(0)                                # (E,)
    aux = E * jnp.sum(frac_tokens * mean_prob)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return aux, z


def moe_ffn(x: jax.Array,
            w_router: jax.Array,
            w_in: jax.Array,
            w_out: jax.Array,
            *,
            k: int = 2,
            capacity_factor: float = 1.25,
            activation: Callable[[jax.Array], jax.Array] = jax.nn.gelu
            ) -> Tuple[jax.Array, MoEMetrics]:
    """Expert-parallel feed-forward block.

    x: (B, S, d).  w_router: (d, E).  w_in: (E, d, ff).  w_out: (E, ff, d) —
    the leading E axis is the one sharded over the ``expert`` mesh axis (see
    ``MOE_RULES``); the two dispatch einsums below are where GSPMD inserts
    the token all-to-alls.
    """
    B, S, d = x.shape
    E = w_router.shape[-1]
    N = B * S
    tokens = x.reshape(N, d)
    gates, logits, _ = topk_router(tokens, w_router, k)
    cap = expert_capacity(N, E, k, capacity_factor)
    dispatch, combine, dropped = _dispatch_tensors(gates, cap)

    xe = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), tokens)  # a2a in
    h = activation(jnp.einsum("ecd,edf->ecf", xe, w_in))
    ye = jnp.einsum("ecf,efd->ecd", h, w_out)
    y = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), ye)        # a2a out

    aux, z = load_balance_loss(gates, logits)
    metrics = MoEMetrics(aux_loss=aux, router_z_loss=z,
                         fraction_dropped=dropped.mean())
    return y.reshape(B, S, d), metrics


# ------------------------------------------------------- dropless experts
class RouterStats(NamedTuple):
    """What one dropless expert layer reports beside its output (scalars)."""
    balance_loss: jax.Array       # E * sum_e f_e P_e
    z_loss: jax.Array             # mean_n logsumexp(router logits)^2
    load_max_over_mean: jax.Array  # most-loaded expert's rows / mean rows


def _take_rows(x, index):
    """``x[index]`` along axis 0 for an ``index`` known to lie in
    ``0 .. len(x) - 1``: every index of the layer comes off a sort over
    an iota (``_sort_by_expert``), whatever the routing.  So the gather
    promises it, and XLA writes no fill and no select over the gathered
    rows for an index that could lie outside."""
    return x.at[index].get(mode="promise_in_bounds")


def _spread_rows(x, order, held_rows=None):
    """(N, d) -> (N k, d): row r is token ``order[r] % N``.  With
    ``held_rows`` (a layer that holds a share of the experts on the
    kernels' path, ``_walks_held_rows``) only the rows before it are
    made: the others are not written (``spread_held_rows``)."""
    token = order % x.shape[0]
    if held_rows is None:
        return _take_rows(x, token)
    return spread_held_rows(x, token, held_rows,
                            interpret=jax.default_backend() != "tpu")


def _sum_slots(rows, inverse, k, held_rows=None):
    """(N k, d) rows in expert order -> (N, d): each token the float32
    sum of its k rows.  ``inverse`` puts them back as the assignments are
    numbered, slot by slot (k runs of N rows), so the k slots are the
    gathered rows' leading axis: splitting it copies nothing, where a k
    in the second-minor dimension of a tiled layout is a copy of all N k
    rows.  With ``held_rows`` only the rows before it are read and summed,
    in the same order (``sum_held_slots``): the others are zero where they
    were written at all."""
    if held_rows is None:
        back = _take_rows(rows, inverse).reshape(k, -1, rows.shape[-1])
        return back.sum(0, dtype=jnp.float32).astype(rows.dtype)
    n = rows.shape[0] // k
    tile = _token_tile(n)
    at, row, starts = _held_by_token(inverse, held_rows, n, k, tile)
    return sum_held_slots(rows, at, row, starts, held_rows, k=k, tile=tile,
                          interpret=jax.default_backend() != "tpu")


# ------------------------------------------- the rows that meet a held expert
# A layer that holds H of E experts sorts the rows of its own experts to the
# front, and ``held_rows = group_sizes[:H].sum()`` of the N k are all that
# the grouped matmuls visit.  The two kernels below are the row gathers with
# a work list that ends there: what they cost follows the rows visited, and a
# router skewed wholly onto the held experts makes them walk all N k (slower,
# never wrong).  A row of a tiled array cannot be copied alone: a slice of
# an HBM array starts and ends on a tile of 8 rows, and two 16-bit rows
# share their 32-bit words.  So the way out holds its (N, d) source in VMEM
# whole and picks a row out of it as 32-bit words, and the way back, whose
# source is the N k rows, fetches a row as the aligned group of
# ``_ROW_GROUP`` rows it lies in, one contiguous copy of 8 x d values, with
# ``_IN_FLIGHT`` such copies under way, and picks it out of the group.
_ROW_GROUP = 8
_IN_FLIGHT = 32
_TOKEN_TILES = (256, 128, 64, 32, 16)
_SOURCE_BYTES = 64 * 2 ** 20    # the (N, d) source the way out holds in VMEM
_VMEM_DEFAULT = 16 * 2 ** 20    # Mosaic's scoped limit where none is set


def _token_tile(n: int) -> Optional[int]:
    """Tokens a grid step of ``sum_held_slots``: the largest that divides."""
    return next((t for t in _TOKEN_TILES if n % t == 0), None)


def _walks_held_rows(n: int, k: int, d: int, f: int, held: int,
                     num_experts: int, dtype) -> bool:
    """Whether a layer's row passes stop at ``held_rows``: it holds a share
    of the experts (else no row can be skipped, and XLA's gathers from
    VMEM beat any copy by row), its rows take the megablox path, whose
    kernels mask the rows behind ``held_rows`` themselves
    (``grouped_matmul``), a value is float32 or its high half, the
    tokens fit the VMEM the way out holds them in, and a list of the N k
    assignments fits the scalar memory (``_one_list``)."""
    return (held < num_experts and jax.default_backend() == "tpu"
            and gmm_tiling(n * k, d, f, jnp.dtype(dtype).itemsize) is not None
            and _token_tile(n) is not None
            and dtype in (jnp.bfloat16, jnp.float32)
            and n * d * jnp.dtype(dtype).itemsize <= _SOURCE_BYTES
            and 4 * n * k <= _LIST_BYTES)


def _walk_params(held_bytes: int):
    """``pallas_call``'s compiler parameters for a kernel that holds
    ``held_bytes`` in VMEM: Mosaic's default scoped limit while they fit
    it with room, else a limit that holds them."""
    from jax.experimental.pallas import tpu as pltpu
    limit = {} if held_bytes + 4 * 2 ** 20 <= _VMEM_DEFAULT else {
        "vmem_limit_bytes": min(held_bytes + 8 * 2 ** 20, 100 * 2 ** 20)}
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",), **limit)


def _picked_row(words, row, pack):
    """Row ``row`` of an array read as (rows / pack, d) uint32 ``words``,
    (1, d) uint32: the value itself (32 bits) or the value in the low
    half (16 bits: two rows share a word, the even row the low half)."""
    word = words[pl.ds(row >> (pack // 2), 1), :]
    if pack == 1:
        return word
    return (word >> (16 * (row & 1)).astype(jnp.uint32)) & jnp.uint32(0xFFFF)


def _spread_kernel(held_ref, token_ref, x_ref, out_ref, stage, *, pack):
    """Grid step i: rows ``i tile .. (i + 1) tile`` of the result, as far
    as ``held_rows`` reaches (to a whole word), each picked out of the
    source ``x_ref``, which VMEM holds whole.  ``stage`` holds the tile as
    words; steps behind the last held tile do nothing, and their block is
    that tile's (the index map stays), so nothing of theirs is written."""
    from jax.experimental.pallas import tpu as pltpu
    tile = out_ref.shape[0]
    first = pl.program_id(0) * tile
    end = jnp.minimum((held_ref[0] + pack - 1) // pack * pack,
                      token_ref.shape[0])
    words = x_ref.bitcast(jnp.uint32)

    @pl.loop(0, jnp.clip(end - first, 0, tile) // pack)
    def _(q):
        word = _picked_row(words, token_ref[first + q * pack], pack)
        if pack == 2:
            word |= _picked_row(words, token_ref[first + q * pack + 1],
                                pack) << jnp.uint32(16)
        stage[pl.ds(q, 1), :] = word

    @pl.when(first < end)
    def _():
        out_ref[...] = pltpu.bitcast(stage[...], out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spread_held_rows(x, token, held_rows, *, interpret=False):
    """x (N, d), token (M,) int32 in ``0 .. N - 1``, held_rows a scalar ->
    (M, d) whose row r is ``x[token[r]]`` for ``r < held_rows``; the rows
    behind are NOT written (the tile ``held_rows`` falls in holds whatever
    the kernel's buffer held, the tiles after it what the memory held).
    Whoever reads the result masks them: megablox's ``gmm`` visits the
    held groups' tiles and selects at the store, ``tgmm`` selects zeros
    for its loaded rows outside the group (DESIGN.md, held experts).  A
    jitted function of this name wraps the call so that the step's
    instruction is ``spread_held_rows.<n>``, as ``gmm.<n>`` is megablox's:
    its result has the sorted rows' shape, by which the experts' metrics
    know their kernels among ``tpu_custom_call.<n>``."""
    from jax.experimental.pallas import tpu as pltpu
    (n, d), (m,) = x.shape, token.shape
    size, tile = x.dtype.itemsize, _GMM_ROW_TILE

    def block(i, held, token):
        return jnp.minimum(i, jnp.maximum(pl.cdiv(held[0], tile) - 1, 0)), 0
    return pl.pallas_call(
        functools.partial(_spread_kernel, pack=4 // size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // tile,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((tile, d), block),
            scratch_shapes=[pltpu.VMEM((tile * size // 4, d), jnp.uint32)]),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=_walk_params((n + 3 * tile) * d * size),
        interpret=interpret, name="spread_held_rows",
    )(held_rows.astype(jnp.int32).reshape(1), token, x)


def _slot_bits(k: int) -> int:
    """Bits that hold a slot ``0 .. k - 1`` under a token in one int32."""
    return (k - 1).bit_length()


def _held_by_token(inverse, held_rows, n, k, tile):
    """The assignments that meet a held expert, listed by token and by slot
    within a token -> (at (N k,), row (N k,), starts (N / tile + 1,)):
    entry e < held_rows is sorted row ``row[e]`` of token ``at[e] >>
    _slot_bits(k)`` (the slot in the low bits), and the tokens of tile j
    are entries ``starts[j] .. starts[j + 1]``.  One sort of the N k, the
    others behind the held."""
    assignment = jax.lax.iota(jnp.int32, n * k)
    at = (assignment % n << _slot_bits(k)) + assignment // n
    at = jnp.where(inverse < held_rows, at, n << _slot_bits(k))
    at, row = jax.lax.sort((at, inverse), num_keys=1)
    edges = jnp.arange(0, n + 1, tile, dtype=jnp.int32) << _slot_bits(k)
    starts = jnp.sum(at[None, :] < edges[:, None], axis=1, dtype=jnp.int32)
    return at, row, starts


def _sum_kernel(held_ref, starts_ref, *refs, pack, slot_bits, tile_bits):
    """Grid step j: tokens ``j tile .. (j + 1) tile``, each the float32 sum
    of its entries in the order they are listed (slot order), zero with
    none.  Entry e's row arrives in slot ``e % _IN_FLIGHT`` of ``buf`` as
    the aligned group it lies in; the fetches run ahead of the entries
    across the steps.  The list is two arrays (``at``, ``row``) or, with
    ``tile_bits``, one: the sorted row above the token's place in its
    tile (``_one_list``)."""
    from jax.experimental.pallas import tpu as pltpu
    *lists, rows_hbm, out_ref, buf, sems, acc = refs
    j = pl.program_id(0)
    held = held_ref[0]

    def row_of(e):
        return lists[1][e] if tile_bits is None else lists[0][e] >> tile_bits

    def fetch(e):
        slot = e & (_IN_FLIGHT - 1)
        start = pl.multiple_of(row_of(e) & -_ROW_GROUP, _ROW_GROUP)
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(start, _ROW_GROUP)],
                                     buf.at[slot], sems.at[slot])

    @pl.when(j == 0)
    def _():
        @pl.loop(0, jnp.minimum(held, _IN_FLIGHT))
        def _(e):
            fetch(e).start()

    acc[...] = jnp.zeros_like(acc)

    @pl.loop(starts_ref[j], starts_ref[j + 1])
    def _(e):
        fetch(e).wait()
        word = _picked_row(buf.at[e & (_IN_FLIGHT - 1)].bitcast(jnp.uint32),
                           row_of(e) & (_ROW_GROUP - 1), pack)
        if pack == 2:           # bfloat16 is float32's high half
            word = word << jnp.uint32(16)
        if tile_bits is None:
            token = pl.ds((lists[0][e] >> slot_bits) - j * out_ref.shape[0],
                          1)
        else:
            token = pl.ds(lists[0][e] & ((1 << tile_bits) - 1), 1)
        acc[token, :] += pltpu.bitcast(word, jnp.float32)

        @pl.when(e + _IN_FLIGHT < held)
        def _():
            fetch(e + _IN_FLIGHT).start()

    out_ref[...] = acc[...].astype(out_ref.dtype)


# The scalar memory a kernel's prefetched lists may take, of the v5e's 1 MiB
# (the tables of ``starts`` and the compiler's own words need the rest).
_LIST_BYTES = 896 * 2 ** 10


def _one_list(m: int, tile: int) -> bool:
    """Whether ``sum_held_slots`` is handed its list as ONE int32 an entry:
    the two arrays of M entries do not fit the scalar memory together
    (M = 163,840: 1.25 MiB), and a sorted row and a token's place in its
    tile fit 31 bits.  Kanana's 98,304 entries fit as two."""
    return 2 * 4 * m > _LIST_BYTES \
        and (m - 1).bit_length() + (tile - 1).bit_length() <= 31


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def sum_held_slots(rows, at, row, starts, held_rows, *, k, tile,
                   interpret=False):
    """rows (M, d) and the list of ``_held_by_token`` -> (N, d): token t
    the float32 sum of ``rows[row[e]]`` over its entries e, in their
    order, and zero for a token with none.  Reads no row behind
    ``held_rows`` and makes no (k, N, d) array.  Named as
    ``spread_held_rows`` is, and for the same reason."""
    from jax.experimental.pallas import tpu as pltpu
    (m, d), n = rows.shape, (starts.shape[0] - 1) * tile
    lists, tile_bits = (at, row), None
    if _one_list(m, tile):
        tile_bits = (tile - 1).bit_length()
        lists = ((row << tile_bits) | ((at >> _slot_bits(k)) & (tile - 1)),)
    return pl.pallas_call(
        functools.partial(_sum_kernel, pack=4 // rows.dtype.itemsize,
                          slot_bits=_slot_bits(k), tile_bits=tile_bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(lists), grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda j, *_: (j, 0)),
            scratch_shapes=[
                pltpu.VMEM((_IN_FLIGHT, _ROW_GROUP, d), rows.dtype),
                pltpu.SemaphoreType.DMA((_IN_FLIGHT,)),
                pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
        compiler_params=_walk_params(d * (
            (2 * tile + _IN_FLIGHT * _ROW_GROUP) * rows.dtype.itemsize
            + 4 * tile)),
        interpret=interpret, name="sum_held_slots",
    )(held_rows.astype(jnp.int32).reshape(1), starts, *lists, rows)


@jax.custom_vjp
def _rows_to_experts(x, order, inverse, held_rows):
    """(N, d) tokens -> (N k, d) rows grouped by expert: row r holds token
    ``order[r] % N``.  ``order`` is a permutation of the N k (slot, token)
    assignments and ``inverse`` its inverse, so the backward is a gather
    and a sum over a token's k slots, not the scatter-add XLA would
    derive from the forward gather.  ``held_rows``: None, or how many of
    the rows, the first, both ways stop at (``_walks_held_rows``)."""
    return _spread_rows(x, order, held_rows)


def _rows_to_experts_fwd(x, order, inverse, held_rows):
    return (_rows_to_experts(x, order, inverse, held_rows),
            (x.shape[0], inverse, held_rows))


def _rows_to_experts_bwd(res, g):
    n, inverse, held_rows = res
    return (_sum_slots(g, inverse, g.shape[0] // n, held_rows), None, None,
            None)


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _experts_to_rows(out, order, inverse, held_rows, k):
    """``_rows_to_experts`` turned round: (N k, d) rows grouped by expert
    -> (N, d) tokens, each the float32 sum of its k rows.  The backward is
    ``_rows_to_experts``' forward, a gather from the (N, d) gradient: it
    reads nothing of ``out``, so no row is saved or recomputed for it."""
    return _sum_slots(out, inverse, k, held_rows)


def _experts_to_rows_fwd(out, order, inverse, held_rows, k):
    return (_experts_to_rows(out, order, inverse, held_rows, k),
            (order, held_rows))


def _experts_to_rows_bwd(k, res, g):
    order, held_rows = res
    return _spread_rows(g, order, held_rows), None, None, None


_experts_to_rows.defvjp(_experts_to_rows_fwd, _experts_to_rows_bwd)


@jax.custom_vjp
def _sort_by_expert(flat_expert, w):
    """The one sort of the layer: (N k,) expert ids and float32 weights,
    one an assignment -> ``(order, inverse, w_sorted)``.  ``order`` lists
    the assignments by expert (stable: as numbered inside an expert),
    ``w_sorted`` is ``w[order]``, carried through the sort beside the iota
    that becomes ``order``, and ``inverse`` is ``order`` sorted back
    against a second iota.  Both are therefore permutations of
    ``0 .. N k - 1`` whatever the routing, which is what lets every gather
    of the layer promise its indices (``_take_rows``).  The backward,
    ``g[inverse]``, is ``g`` sorted back by ``order``.  No gather of N k
    scalars is made either way (0.7 ms for 98,304 on the v5e, where the
    sort that carries them takes 0.1), and no scatter-add, which is what
    the sort's own derivative would be."""
    iota = jax.lax.iota(jnp.int32, flat_expert.shape[0])
    _, order, w_sorted = jax.lax.sort((flat_expert, iota, w), num_keys=1,
                                      is_stable=True)
    _, inverse = jax.lax.sort((order, iota), num_keys=1)
    return order, inverse, w_sorted


def _sort_by_expert_fwd(flat_expert, w):
    out = _sort_by_expert(flat_expert, w)
    return out, out[0]


def _sort_by_expert_bwd(order, g):
    return None, jax.lax.sort((order, g[2]), num_keys=1)[1]


_sort_by_expert.defvjp(_sort_by_expert_fwd, _sort_by_expert_bwd)


def _sorted_assignments(expert_idx, weights, num_experts, first_held):
    """expert_idx, weights (N, k) -> (order, inverse, w_sorted (N k,),
    group_sizes (E,)): the N k assignments listed by expert, the experts
    from ``first_held`` on first.  Assignment ``a = slot * N + token``:
    the slots lead, so that the way back splits a leading axis
    (``_sum_slots``); inside an expert the rows then lie by slot and by
    token within a slot.  The counts are one compare of the N k ids with
    the E experts, summed: no scatter-add of N k ones into E bins."""
    n, k = expert_idx.shape
    flat_expert = expert_idx.T.reshape(k * n)
    if first_held:
        flat_expert = (flat_expert - first_held) % num_experts
    order, inverse, w_sorted = _sort_by_expert(
        flat_expert, weights.astype(jnp.float32).T.reshape(k * n))
    experts = jnp.arange(num_experts, dtype=flat_expert.dtype)
    group_sizes = jnp.sum(flat_expert[:, None] == experts, axis=0,
                          dtype=jnp.int32)
    return order, inverse, w_sorted, group_sizes


class HeldStats(NamedTuple):
    """What an expert layer that holds a share of the experts reports
    beside its output (scalars)."""
    held_rows: jax.Array          # rows of the N k that went to held experts
    load_max_over_mean: jax.Array  # among the held: most-loaded / mean rows
    choice_share_held: jax.Array  # held_rows / (N k): H / E when even
    tile_fill: jax.Array          # held_rows / rows multiplied (``tile_fill``)


# megablox's tile (rows, contracted, output), from what the chip said.
# PR 27, at the OLMoE cell's shape (65,536 rows in 64 groups, 2,048 <->
# 1,024): of (128, 128, 128), (512, 512, 512), (512, 1024, 1024) and (1024,
# 1024, 1024) the third was fastest; a dimension 1,024 does not divide
# (768-wide experts) takes the largest of ``_GMM_TILES`` that does (PR 34).
# PR 62, at the Qwen3-Next cell's shape (163,840 rows, 64 of 512 groups
# held, 2,048 <-> 512) and Kanana's (98,304 rows, 16 of 128, 2,048 <-> 768),
# over the counts their routers really gave (a Qwen3-Next window goes from
# 320 to 2,300 rows a held group as its router turns to the experts that are
# there) and row tiles of 512 / 256 / 128 beside split and whole blocks:
# where VMEM holds a group's WHOLE (d, f) matrix beside 256 rows, each of
# the three products is faster at every count (7-32%; the three of a shape
# together 13-26%): a visit is one grid step, a group's matrix stays in VMEM
# over its consecutive row tiles and the float32 accumulator is written
# once.  Beside a whole block 256 rows beat 512 by 3% where a group holds
# ~2,000 rows and by 17% where it holds ~320; 128 win another 3% there and
# lose 5% at ~2,000.  Beside a split block (OLMoE: 2,048 x 1,024 is too
# large whole) 256 and 512 rows differ by 2%, neither in all three
# products, and 512 stay.  The rows a group holds decided nothing the
# block's size had not (PERF.md section 6, PR 62).
_GMM_ROW_TILE = 512             # a megablox shape's rows are a multiple
_GMM_ROW_TILES = (_GMM_ROW_TILE, 256)   # beside a split block, a whole one
_GMM_TILES = (1024, 768, 512, 384, 256, 128)


def _gmm_vmem_bytes(rows: int, d: int, f: int, itemsize: int) -> int:
    """What the hungriest of megablox's three kernels holds in VMEM at the
    tile (rows, d, f): its operand and result blocks twice each (the
    pipeline's two buffers) and a float32 accumulator the result's size:
    rows x f forward, rows x d for the rows' gradient, d x f for the
    weights'.  Mosaic refused every tile tried that this puts over its 16
    MiB and took every one under 15 (the described v5e, PR 62)."""
    blocks = 2 * itemsize * (rows * d + rows * f + d * f)
    return blocks + 4 * max(rows * d, rows * f, d * f)


def gmm_tiling(m: int, d: int, f: int,
               itemsize: int = 2) -> Optional[Tuple[int, int, int]]:
    """megablox's (rows, contracted, output) tile for an (m, d) x (d, f)
    product over ragged groups, the same numbers for the three products of
    a shape (the rows' gradient takes the last two swapped): the group's
    whole matrix beside 256 rows where VMEM holds that for operands of
    ``itemsize`` bytes, else 512 rows beside the largest of ``_GMM_TILES``
    that divides each dimension.  A dimension that no 128-lane tile
    divides (Nemotron-H's 1,856-wide experts: 29 x 64) is taken whole, as a
    block may be whatever its width, where VMEM holds the tile that makes
    (2,688 <-> 1,856: 512 rows, 384 of the 2,688, all 1,856).  None where
    nothing fits or ``m`` is no multiple of 512 (the caller then takes
    ``ragged_dot``): a decode step's 16-192 rows never come here."""
    def tile(dim):
        return next((t for t in _GMM_TILES if dim % t == 0),
                    None if dim % 16 else dim)
    tile_d, tile_f = tile(d), tile(f)
    if m % _GMM_ROW_TILE or tile_d is None or tile_f is None:
        return None
    split, whole = _GMM_ROW_TILES
    for tiling in ((whole, d, f), (split, tile_d, tile_f)):
        if _gmm_vmem_bytes(*tiling, itemsize) <= _VMEM_DEFAULT - 2 ** 20:
            return tiling
    return None


def gmm_visits(group_sizes, held: int, row_tile: int):
    """Grid steps along the rows of one ``gmm`` call over the ``held``
    leading groups: the kernel visits a row tile once for every group that
    touches it (``make_group_metadata``: a group's tiles run from its start
    rounded down to its end rounded up, an empty group has none) and
    multiplies the whole tile each time."""
    ends = jnp.cumsum(group_sizes[:held])
    starts = ends - group_sizes[:held]
    tiles = (ends + row_tile - 1) // row_tile - starts // row_tile
    return jnp.where(ends > starts, tiles, 0).sum()


def tile_fill(group_sizes: jax.Array, held: int,
              tiling: Optional[Tuple[int, int, int]]) -> jax.Array:
    """How well the row tile fits the routing a step really had: of the
    rows megablox multiplies for the ``held`` leading groups, the share
    that lay in the visit's own group, ``held_rows / (visits x rows a
    tile)`` (``gmm_visits``).  1 where every held group starts and ends on
    a tile's edge, towards 0 as the groups grow small beside the tile; 0
    with no held row, and 1 where no tile is chosen (``ragged_dot`` walks
    no tile of ours)."""
    if tiling is None:
        return jnp.float32(1.0)
    rows = gmm_visits(group_sizes, held, tiling[0]) * tiling[0]
    return group_sizes[:held].sum() / jnp.maximum(rows, 1).astype(jnp.float32)


@jax.custom_vjp
def _megablox(rows, w, group_sizes):
    """megablox's ``gmm`` with each of its three products tiled for its
    own shape (megablox's own vjp hands the forward's tile to both
    backward products, whose contracted and output dimensions are the
    forward's swapped).  ``w`` may hold fewer groups than ``group_sizes``
    counts: the leading ones, and rows of the others come out zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    (m, d), f = rows.shape, w.shape[-1]
    return gmm(rows, w, group_sizes, rows.dtype,
               gmm_tiling(m, d, f, rows.dtype.itemsize))


def _megablox_fwd(rows, w, group_sizes):
    return _megablox(rows, w, group_sizes), (rows, w, group_sizes)


def _megablox_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    rows, w, group_sizes = res
    (m, d), f, size = rows.shape, w.shape[-1], rows.dtype.itemsize
    d_rows = gmm(g, w, group_sizes, rows.dtype, gmm_tiling(m, f, d, size),
                 transpose_rhs=True)
    d_w = tgmm(rows.swapaxes(0, 1), g, group_sizes, w.dtype,
               gmm_tiling(m, d, f, size), num_actual_groups=w.shape[0])
    return d_rows, d_w, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def _sizes_in_stack(group_sizes, held: int, stack_groups: int, at):
    """A layer's counts (E,) as counts over the groups of its whole stack:
    the ``held`` leading ones at groups ``at x held ..``, every other
    layer's group empty (an empty group costs ``gmm`` no grid step).  Where
    the layer holds a share, one more group stands behind the stack's, as
    the groups not held stand behind ``w``'s in ``_megablox``: megablox then
    zeroes the rows that no held group covers."""
    sizes = jnp.zeros(stack_groups + (held < group_sizes.shape[0]),
                      group_sizes.dtype)
    return jax.lax.dynamic_update_slice(sizes, group_sizes[:held],
                                        (at * held,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _megablox_at(rows, w, stack, sizes_in_stack, group_sizes, held):
    """``_megablox`` for a layer whose ``held`` matrices ``w`` (H, d, f) are
    one layer of ``stack`` (layers x H, d, f), the same leaf whole.  A
    kernel's operand is a buffer, so a slice of the stack handed to it is
    copied whole; every product here that reads the weights reads ``stack``
    in place instead, under the layer's counts laid among the stack's
    groups (``_sizes_in_stack``).  ``w``'s VALUE IS NEVER READ: it is the
    operand the layer's cotangent goes to, one ``tgmm`` result of ``w``'s
    own shape over the layer's own counts, as in ``_megablox``; a slice
    nothing reads is dead code in a scan's body.  ``stack`` gets no
    cotangent (the caller stops its gradient)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    (m, d), f = rows.shape, stack.shape[-1]
    return gmm(rows, stack, sizes_in_stack, rows.dtype,
               gmm_tiling(m, d, f, rows.dtype.itemsize))


def _megablox_at_fwd(rows, w, stack, sizes_in_stack, group_sizes, held):
    return (_megablox_at(rows, w, stack, sizes_in_stack, group_sizes, held),
            (rows, stack, sizes_in_stack, group_sizes))


def _megablox_at_bwd(held, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    rows, stack, sizes_in_stack, group_sizes = res
    (m, d), f, size = rows.shape, stack.shape[-1], rows.dtype.itemsize
    d_rows = gmm(g, stack, sizes_in_stack, rows.dtype,
                 gmm_tiling(m, f, d, size), transpose_rhs=True)
    d_w = tgmm(rows.swapaxes(0, 1), g, group_sizes, stack.dtype,
               gmm_tiling(m, d, f, size), num_actual_groups=held)
    return d_rows, d_w, None, None, None


_megablox_at.defvjp(_megablox_at_fwd, _megablox_at_bwd)


def grouped_matmul(rows: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   stack: Optional[Tuple[jax.Array, jax.Array]] = None
                   ) -> jax.Array:
    """rows (M, d) sorted by group, w (H, d, f), group_sizes (E,) with
    H <= E -> (M, f): row r times the matrix of the group it lies in.
    ``w`` holds the first H of the E groups; rows of the other groups
    (they lie behind the held ones) cost no matmul and come out zero.

    ``stack`` (a layer of a training scan): ``(whole, sizes)``, the leaf
    that ``w`` is a layer of, viewed ``(layers x H, d, f)`` with its
    gradient stopped, and the layer's counts laid among its groups
    (``_sizes_in_stack``).  Where the kernels below run and ``whole`` is
    stored in the rows' type, they read it in place and ``w`` only names
    where the gradient goes (``_megablox_at``); elsewhere ``w`` is read as
    it always was (a cast of the slice is work and no copy, and XLA's own
    ``ragged_dot`` reads a slice where it lies), so values and gradients
    are those of the call without ``stack``.

    On a TPU, where a tile divides the shapes (``gmm_tiling``): megablox's
    Pallas ``gmm`` (kernels ``gmm`` and, for the weights' gradient,
    ``tgmm``), which read the transposed weights in place for the rows'
    gradient, each of the three products under the tile of its own shape:
    a group's whole matrix beside 256 rows where VMEM holds it (512-wide
    and 768-wide experts of a 2,048-wide model), 512 rows beside blocks of
    up to 1,024 otherwise; ``tile_fill`` says how the row tile fits the
    counts a step had.  Elsewhere ``jax.lax.ragged_dot``, which XLA lowers
    on a TPU to its own kernels (``ragged-dot*``): slower there by a fifth
    to a third in all three products, and its backward copies the weights
    transposed; with ``H < E`` its rows behind the held groups are set to
    zero here, which the TPU's kernel does not do itself.
    """
    (m, d), (held, _, f) = rows.shape, w.shape
    if jax.default_backend() == "tpu" and gmm_tiling(
            m, d, f, rows.dtype.itemsize):
        if stack is not None and stack[0].dtype == rows.dtype:
            return _megablox_at(rows, w, *stack, group_sizes, held)
        return _megablox(rows, w, group_sizes)
    out = jax.lax.ragged_dot(rows, w, group_sizes[:held])
    if jax.default_backend() == "tpu" and held < group_sizes.shape[0]:
        # XLA's TPU kernel writes the rows its groups cover and no other:
        # behind the held groups lies what the buffer held (NaNs, seen on
        # the v5e at a decode step's 64 rows: PERF.md, PR 52).  The CPU's
        # ragged_dot zeroes them, and a share of the experts had met
        # ragged_dot there only (Kanana trains through megablox)
        covered = jnp.arange(m)[:, None] < group_sizes[:held].sum()
        out = jnp.where(covered, out, 0)
    return out


# ------------------------------------------------------ the router's choice
# ``lax.top_k`` of k in E sorts every row of E with an iota payload and slices
# k off (1.3-1.5 ms a call at (16384, 512) on the v5e), ``take_along_axis``
# gathers the N k chosen numbers one by one (8 ns each) and the backward of
# either is a scatter-add into a zeroed (N, E).  The kernel below holds a tile
# of tokens in VMEM and takes k maxima off it; the backward is a select.
_CHOICE_TOKENS = 256            # tokens a grid step of ``router_choice``


def _choice_kernel(*refs, k, same):
    """Grid step i: tokens ``i tile .. (i + 1) tile``.  The block arrives
    (tile, E) as the router made it and is turned (E, tile) in VMEM: the
    experts along the sublanes, a token a lane, so a row's maximum is
    elementwise across E / 8 vregs and a reduce inside one, and a round's
    result is a whole lane row of the (k, N) results.  k rounds: the
    maximum, the FIRST expert that holds it (``lax.top_k``'s tie), the
    payload there (a sum of one term), the expert struck out.  A key of
    ``-inf`` (a group that ``_within_best_groups`` shut out) reads as the
    least finite number, so a struck expert is never met again and a
    row's k are distinct whatever it holds."""
    keys_ref, *payload_ref, idx_ref, picked_ref = refs
    x = jnp.maximum(keys_ref[...].T, jnp.finfo(jnp.float32).min)
    payload = None if same else payload_ref[0][...].T
    num_experts = x.shape[0]
    # ids as float32, exact below 2 ** 24: the vector unit has a float
    # minimum and makes an integer one of a compare and a select
    expert = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0).astype(
        jnp.float32)
    for j in range(k):
        best = jnp.max(x, axis=0, keepdims=True)
        idx = jnp.min(jnp.where(x == best, expert, num_experts), axis=0,
                      keepdims=True)
        hit = expert == idx
        idx_ref[j:j + 1, :] = idx.astype(jnp.int32)
        picked_ref[j:j + 1, :] = best if same else jnp.sum(
            jnp.where(hit, payload, 0.0), axis=0, keepdims=True)
        x = jnp.where(hit, -jnp.inf, x)


@functools.lru_cache(maxsize=None)
def _router_choice(n: int, num_experts: int, k: int, same: bool,
                   interpret: bool):
    """The kernel at one shape: keys (and, unless they are the ``same``,
    the payload) (N, E) float32 -> (expert_idx int32, picked float32), each
    (k, N) in rows of whole sublane tiles, which the caller slices and
    turns.  The ``pallas_call`` stands inside a jitted function of the
    kernel's name so that the v5e's trace of a step prints it
    (``ops/delta_rule._kernel``); kept, so that a step traces the body once
    a shape however often ``custom_vjp`` and a checkpoint ask for it."""
    from jax.experimental.pallas import tpu as pltpu
    tile, rows = _CHOICE_TOKENS, -(-k // 8) * 8
    by_token = pl.BlockSpec((rows, tile), lambda i: (0, i))

    def router_choice(*operands):
        return pl.pallas_call(
            functools.partial(_choice_kernel, k=k, same=same),
            grid=(n // tile,),
            in_specs=[pl.BlockSpec((tile, num_experts), lambda i: (i, 0))]
            * len(operands),
            out_specs=[by_token, by_token],
            out_shape=[jax.ShapeDtypeStruct((rows, n), jnp.int32),
                       jax.ShapeDtypeStruct((rows, n), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret, name="router_choice")(*operands)
    return jax.jit(router_choice)


def _choice_by_kernel(keys, payload, k: int, *, interpret: bool = False):
    """``choose_experts``' results from the kernel."""
    operands = (keys,) if payload is None else (keys, payload)
    idx, picked = _router_choice(*keys.shape, k, payload is None,
                                 interpret)(*operands)
    return idx[:k].T, picked[:k].T


def _choice_in_kernel(n: int, num_experts: int) -> bool:
    """Whether a call's experts are picked by the kernel: on a TPU, the
    experts whole 128-lane blocks and the tokens whole tiles (Qwen3-Next's
    and Kanana's steps, SDAR's and Keye's prompts).  Everything else takes
    ``lax.top_k``: the CPU, 64 or 32 experts (OLMoE, LFM2, Ling, Trinity),
    a decode step's 4-128 rows."""
    return (jax.default_backend() == "tpu" and num_experts % 128 == 0
            and n > 0 and n % _CHOICE_TOKENS == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _choose(keys, payload, k, same):
    """``choose_experts`` on keys whose gradient is stopped; ``same``: the
    keys are the payload too, and ``payload`` only names where the
    gradient goes."""
    if _choice_in_kernel(*keys.shape):
        return _choice_by_kernel(keys, None if same else payload, k,
                                 interpret=jax.default_backend() != "tpu")
    vals, idx = jax.lax.top_k(keys, k)
    return idx, vals if same else jnp.take_along_axis(payload, idx, axis=-1)


def _choose_fwd(keys, payload, k, same):
    idx, picked = _choose(keys, payload, k, same)
    return (idx, picked), (idx, keys.shape[1])


def _choose_bwd(k, same, res, cts):
    idx, num_experts = res
    expert = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], num_experts),
                                      1)
    d_payload = jnp.zeros(expert.shape, cts[1].dtype)
    for j in range(k):
        # + and not a select alone: a scatter-add's 0 + -0.0 is +0.0
        d_payload += jnp.where(expert == idx[:, j:j + 1],
                               cts[1][:, j:j + 1], 0)
    return None, d_payload


_choose.defvjp(_choose_fwd, _choose_bwd)


def choose_experts(keys: jax.Array, payload: Optional[jax.Array], k: int):
    """-> (expert_idx (N, k) int32, picked (N, k) float32): the k largest
    ``keys`` (N, E) of every row in falling order, of equal keys the lower
    id first, as ``lax.top_k`` names them, and ``payload`` (N, E) there;
    None: the keys themselves.  The keys decide and carry no gradient; the
    payload's is ``d picked`` put where it was picked, as a select over
    (N, E) in one fusion: a row's k are distinct, so it equals the
    scatter-add of ``top_k``'s and ``take_along_axis``'s own derivative bit
    for bit.  Which forward runs is read from the call
    (``_choice_in_kernel``)."""
    same = payload is None
    return _choose(jax.lax.stop_gradient(keys), keys if same else payload,
                   k, same)


def route_softmax(x: jax.Array, w_router: jax.Array, k: int,
                  norm_topk: bool = False):
    """-> (expert_idx (N, k), weights (N, k), logits, probs (N, E)): a
    float32 softmax over all E experts whose top-k probabilities weigh
    the experts' outputs as they are (OLMoE), or with ``norm_topk``
    divided by their sum over the k chosen (Qwen3-Next's
    ``norm_topk_prob``): over ALL the chosen, whichever of them a layer
    that holds a share of the experts holds."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)         # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx, gate_vals = choose_experts(probs, None, k)       # (N, k)
    if norm_topk:
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    return expert_idx, gate_vals, logits, probs


def route_sigmoid(x: jax.Array, w_router: jax.Array, select_bias: jax.Array,
                  k: int, weight_scale: float, eps: float = 1e-20,
                  n_group: int = 1, topk_group: int = 1):
    """-> (expert_idx (N, k), weights (N, k)): float32 sigmoid scores over
    all E experts; the k chosen are the top of score + ``select_bias``
    (the bias decides the choice and never a weight: it balances the load
    with no auxiliary loss and has no gradient), and their weights are
    the scores alone, renormalised over the k chosen and scaled.
    ``eps`` is what the family adds to the divisor (DeepSeek-V3's 1e-20,
    LFM2's 1e-6): part of its arithmetic, not a setting.

    ``n_group`` > 1 (DeepSeek-V3's group-limited routing): the experts lie
    in ``n_group`` equal groups by id, a group's score is the sum of its
    two largest score + bias, and the k are chosen among the experts of
    the ``topk_group`` best groups.  At ``n_group`` 1 nothing of that is
    traced."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)         # (N, E)
    scores = jax.nn.sigmoid(logits)
    select = scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32))
    if n_group > 1:
        select = _within_best_groups(select, n_group, topk_group)
    expert_idx, chosen = choose_experts(select, scores, k)
    weights = chosen / (chosen.sum(-1, keepdims=True) + eps)
    return expert_idx, weights * weight_scale


def choice_of_live_rows(expert_idx: jax.Array, live: jax.Array) -> jax.Array:
    """expert_idx (N, k) with the rows that are not ``live`` (N,) bool (a
    decode step's rows padded up to its bucket) given the first live row's
    choice: a padded row then lies in groups that a live row opened, so
    the grouped matmuls read no expert for it that no sequence chose (at
    a decode step's row counts an expert's weights read is the cost)."""
    with jax.named_scope("moe_router"):
        return jnp.where(live[:, None], expert_idx,
                         expert_idx[jnp.argmax(live)])


def dropless_experts(x: jax.Array, expert_idx: jax.Array, weights: jax.Array,
                     w_gate: Optional[jax.Array], w_up: jax.Array,
                     w_down: jax.Array, *, num_experts: int,
                     first_held: int = 0, stack: Optional[tuple] = None):
    """Each token through those of its chosen experts that are held here,
    weighted and summed -> (y (N, d), group_sizes (E,)).

    Which expert runs is read from the call.  With ``w_gate``: SwiGLU,
    three matrices, ``down(silu(gate(x)) * up(x))``.  With ``w_gate`` None:
    the two-matrix form ``down(relu(up(x)) ** 2)`` (Nemotron-H's ``relu2``
    experts, which have no gate matrix): two grouped matmuls forward and
    four backward, and no zero or unit matrix in the gate's place.  What
    follows holds for both but the hidden rows' formula.

    ``w_gate``, ``w_up`` (H, d, f) and ``w_down`` (H, f, d) are experts
    ``first_held .. first_held + H - 1`` of the ``num_experts`` the router
    chose among (all of them when H == E): the share of an expert-
    parallel layer that this chip holds.  The N k assignments are sorted
    by expert, the held experts' first, in one sort that returns the order
    and each row's weight with it (``_sort_by_expert``; the order sorted
    back is its inverse).  Both are permutations of the assignments
    because a sort over an iota made them, so the gathers that carry the
    rows to the experts and back promise their indices and nothing is
    filled, selected or scattered.  Three grouped matmuls (two) run over
    the held groups, and the rows are put back, a token's k slots leading,
    and summed per token in float32.  The weight multiplies the hidden
    rows ``silu(gate) * up`` (``relu(up) ** 2``) in float32, inside the
    fusion that makes them, and not the output rows: the down projection
    is linear,
    so ``down(w h) = w down(h)``, and the combine is then the dispatch
    transposed (``_experts_to_rows``), whose gradient reads no output row.
    (A weight on the output rows has the gradient ``sum_d out * g``: a
    checkpointed layer would run the down projection a second time in
    its backward only to feed that product.)  A choice of an absent
    expert costs no matmul and adds nothing (its rows come out of
    ``grouped_matmul`` zero): what that expert would add is another
    chip's part, and on several chips the exchange of rows goes between
    the sort and the matmuls (DESIGN.md, held experts).  With H < E on the
    megablox path the rows that meet no held expert (they lie behind
    ``held_rows = group_sizes[:H].sum()``) are neither carried to the
    experts nor fetched back (``_walks_held_rows``).  No capacity: no
    (N, E, C) tensor exists and nothing is dropped among the held,
    whatever the imbalance.  ``group_sizes[i]`` counts the rows of expert
    ``first_held + i`` (mod E).  ``stack`` (a layer of a training scan):
    ((the leaves whole in the arguments' order, three or two, each
    ``(layers x H, ...)`` with its gradient stopped), this layer's index,
    traced or not): what ``grouped_matmul`` may read in place of the slices.
    """
    (n, d), k, (held, _, f) = x.shape, expert_idx.shape[1], w_up.shape
    in_gate = in_up = in_down = None          # ``grouped_matmul``'s stack
    with jax.named_scope("moe_dispatch"):
        order, inverse, w_sorted, group_sizes = _sorted_assignments(
            expert_idx, weights, num_experts, first_held)
        held_rows = None
        if _walks_held_rows(n, k, d, f, held, num_experts, x.dtype):
            held_rows = group_sizes[:held].sum()
        rows = _rows_to_experts(x, order, inverse, held_rows)    # (N k, d)
        if stack is not None:
            leaves, at = stack
            sizes = _sizes_in_stack(group_sizes, held, leaves[0].shape[0], at)
            in_gate, in_up, in_down = [None] * (3 - len(leaves)) + [
                (whole, sizes) for whole in leaves]
    with jax.named_scope("moe_experts"):
        gate = None if w_gate is None else grouped_matmul(
            rows, w_gate.astype(x.dtype), group_sizes, in_gate)
        up = grouped_matmul(rows, w_up.astype(x.dtype), group_sizes, in_up)
        if gate is None:
            hidden = jnp.square(jax.nn.relu(up.astype(jnp.float32)))
        else:
            hidden = (jax.nn.silu(gate.astype(jnp.float32))
                      * up.astype(jnp.float32))
        hidden = hidden * w_sorted[:, None]
        out = grouped_matmul(hidden.astype(x.dtype), w_down.astype(x.dtype),
                             group_sizes, in_down)
    with jax.named_scope("moe_combine"):
        y = _experts_to_rows(out, order, inverse, held_rows, k)
    return y, group_sizes


def dropless_moe_ffn(x: jax.Array, w_router: jax.Array,
                     w_gate: Optional[jax.Array], w_up: jax.Array,
                     w_down: jax.Array, *, k: int,
                     scoring: str = "softmax", norm_topk: bool = False,
                     select_bias: Optional[jax.Array] = None,
                     weight_scale: float = 1.0, first_held: int = 0,
                     choices: bool = False,
                     live: Optional[jax.Array] = None,
                     stack_at=None, stack: Optional[tuple] = None):
    """Token-choice experts with no capacity: every token is computed by
    each of its top-k experts that is held here, whatever the imbalance.
    SwiGLU experts, or with ``w_gate`` None the two-matrix ``relu ** 2``
    ones (``dropless_experts``).

    x (N, d); w_router (d, E): the router always has its full width.
    w_gate, w_up (H, d, f); w_down (H, f, d): the H <= E experts held
    here, ``first_held`` the first (``dropless_experts``).  ``scoring``
    ``"softmax"`` (``route_softmax``, with ``norm_topk`` the weights
    renormalised over the k chosen) returns ``RouterStats`` over all E
    where every expert is held and ``HeldStats`` where the layer holds a
    share (H < E: the auxiliary terms are sums over all the experts'
    rows, which one share does not see); ``"sigmoid"`` (``route_sigmoid``
    with ``select_bias`` (E,) and ``weight_scale``) has no auxiliary term
    and returns ``HeldStats``.
    ``choices`` (a serving step): a third result, the expert ids (N, k)
    int32 that made ``y``; ``live`` (N,) bool with it: the rows that are
    some sequence's (``choice_of_live_rows``).
    ``stack_at`` (a traced scalar, a serving forward): the weights are the
    experts of a whole STACK of layers as one run of groups, (layers x E,
    ...), and this layer's are groups ``stack_at x E .. stack_at x E + E -
    1``; the other layers' groups are empty (a kernel's operand is a
    buffer: a layer's experts sliced out of their stack by a scan would be
    copied whole).  The stats are then this layer's, over its own E.
    ``stack`` (a training forward's layer scan, for the same reason): the
    weights are the layer's own slice and ``stack`` is ((the three leaves
    of the whole stack, ``models/_common.experts_in_place``), the layer's
    index, traced): the grouped matmuls read the layer's groups in the
    stack and the slice's value is read by nobody, where
    ``grouped_matmul`` says; the counts, the sort and the stats are over
    the layer's own E either way.
    """
    n = x.shape[0]
    num_experts, held = w_router.shape[-1], w_up.shape[0]
    if stack_at is not None:
        held = num_experts
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown scoring {scoring!r} "
                         "(expected softmax | sigmoid)")
    with jax.named_scope("router"):
        if scoring == "softmax":
            route = functools.partial(route_softmax, norm_topk=True) \
                if norm_topk else route_softmax
            expert_idx, weights, logits, probs = route(x, w_router, k)
        else:
            expert_idx, weights = route_sigmoid(x, w_router, select_bias, k,
                                                weight_scale)
    if live is not None:
        expert_idx = choice_of_live_rows(expert_idx, live)
    if stack_at is None:
        y, group_sizes = dropless_experts(
            x, expert_idx, weights, w_gate, w_up, w_down,
            num_experts=num_experts, first_held=first_held, stack=stack)
    else:
        y, group_sizes = dropless_experts(
            x, expert_idx + stack_at * num_experts, weights, w_gate, w_up,
            w_down, num_experts=w_up.shape[0])
        group_sizes = jax.lax.dynamic_slice_in_dim(
            group_sizes, stack_at * num_experts, num_experts)
    chose = (expert_idx.astype(jnp.int32),) if choices else ()
    with jax.named_scope("router"):
        if scoring == "sigmoid" or held < num_experts:
            mine = group_sizes[:held].astype(jnp.float32)
            rows = mine.sum()
            tiling = gmm_tiling(n * k, *w_up.shape[1:], x.dtype.itemsize)
            return (y, HeldStats(rows, mine.max() / jnp.maximum(mine.mean(),
                                                                1e-9),
                                 rows / (n * k),
                                 tile_fill(group_sizes, held, tiling)),
                    *chose)
        if first_held:
            group_sizes = jnp.roll(group_sizes, first_held)
        share = group_sizes.astype(jnp.float32) / (n * k)        # f_e
        balance = num_experts * jnp.sum(share * probs.mean(0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        load = share.max() * num_experts
    return (y, RouterStats(balance, z, load), *chose)


# Sharding rules for MoE params (compose with TRANSFORMER_RULES by
# prepending these — first match wins).
MOE_RULES = [
    # stacked-per-layer variants FIRST (first match wins, and the generic
    # patterns below would also fullmatch these paths)
    (r".*blocks/moe/router$", P("pipeline", None, None)),
    (r".*blocks/moe/w_in$",   P("pipeline", "expert", "fsdp", "tensor")),
    (r".*blocks/moe/w_out$",  P("pipeline", "expert", "tensor", "fsdp")),
    (r".*moe/router$",   P(None, None)),            # (d, E) replicated
    (r".*moe/w_in$",     P("expert", "fsdp", "tensor")),
    (r".*moe/w_out$",    P("expert", "tensor", "fsdp")),
]


def init_moe_params(rng: jax.Array, d_model: int, d_ff: int,
                    num_experts: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    kr, ki, ko = jax.random.split(rng, 3)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": (jax.random.normal(kr, (d_model, num_experts)) * 0.02
                   ).astype(dtype),
        "w_in": (jax.random.normal(ki, (num_experts, d_model, d_ff))
                 * scale_in).astype(dtype),
        "w_out": (jax.random.normal(ko, (num_experts, d_ff, d_model))
                  * scale_out).astype(dtype),
    }


def _within_best_groups(select: jax.Array, n_group: int,
                        topk_group: int) -> jax.Array:
    """select (N, E) with the experts outside each row's ``topk_group``
    best groups (of ``n_group`` by id; a group's score the sum of its two
    largest entries) at -inf: no top-k over it names one of them."""
    n, e = select.shape
    by_group = select.reshape(n, n_group, e // n_group)
    group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)          # (N, G)
    _, best = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], best].set(True)
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(n, e)
