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
with the E experts, summed; nothing of the layer is a scatter.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
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


def _spread_rows(x, order):
    """(N, d) -> (N k, d): row r is token ``order[r] % N``."""
    return _take_rows(x, order % x.shape[0])


def _sum_slots(rows, inverse, k):
    """(N k, d) rows in expert order -> (N, d): each token the float32
    sum of its k rows.  ``inverse`` puts them back as the assignments are
    numbered, slot by slot (k runs of N rows), so the k slots are the
    gathered rows' leading axis: splitting it copies nothing, where a k
    in the second-minor dimension of a tiled layout is a copy of all N k
    rows."""
    back = _take_rows(rows, inverse).reshape(k, -1, rows.shape[-1])
    return back.sum(0, dtype=jnp.float32).astype(rows.dtype)


@jax.custom_vjp
def _rows_to_experts(x, order, inverse):
    """(N, d) tokens -> (N k, d) rows grouped by expert: row r holds token
    ``order[r] % N``.  ``order`` is a permutation of the N k (slot, token)
    assignments and ``inverse`` its inverse, so the backward is a gather
    and a sum over a token's k slots, not the scatter-add XLA would
    derive from the forward gather."""
    return _spread_rows(x, order)


def _rows_to_experts_fwd(x, order, inverse):
    return _rows_to_experts(x, order, inverse), (x.shape[0], inverse)


def _rows_to_experts_bwd(res, g):
    n, inverse = res
    return _sum_slots(g, inverse, g.shape[0] // n), None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _experts_to_rows(out, order, inverse, k):
    """``_rows_to_experts`` turned round: (N k, d) rows grouped by expert
    -> (N, d) tokens, each the float32 sum of its k rows.  The backward is
    ``_rows_to_experts``' forward, a gather from the (N, d) gradient: it
    reads nothing of ``out``, so no row is saved or recomputed for it."""
    return _sum_slots(out, inverse, k)


def _experts_to_rows_fwd(out, order, inverse, k):
    return _experts_to_rows(out, order, inverse, k), order


def _experts_to_rows_bwd(k, order, g):
    return _spread_rows(g, order), None, None


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


# megablox tiles, largest first.  A row tile of 512 and contracted and
# output tiles of 1,024 were the fastest of those tried on the v5e at the
# OLMoE cell's shape that fit VMEM (PERF.md section 6, PR 27); a dimension
# those do not divide (768-wide experts) takes the largest that does.
_GMM_ROW_TILE = 512
_GMM_TILES = (1024, 768, 512, 256, 128)


def gmm_tiling(m: int, d: int, f: int) -> Optional[Tuple[int, int, int]]:
    """megablox's (rows, contracted, output) tile for an (m, d) x (d, f)
    product over ragged groups: the tile follows the shape.  None where
    no tile divides (the caller then takes ``ragged_dot``)."""
    def tile(dim):
        return next((t for t in _GMM_TILES if dim % t == 0), None)
    tiling = (_GMM_ROW_TILE if m % _GMM_ROW_TILE == 0 else None,
              tile(d), tile(f))
    return None if None in tiling else tiling


@jax.custom_vjp
def _megablox(rows, w, group_sizes):
    """megablox's ``gmm`` with each of its three products tiled for its
    own shape (megablox's own vjp hands the forward's tile to both
    backward products, whose contracted and output dimensions are the
    forward's swapped).  ``w`` may hold fewer groups than ``group_sizes``
    counts: the leading ones, and rows of the others come out zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    (m, d), f = rows.shape, w.shape[-1]
    return gmm(rows, w, group_sizes, rows.dtype, gmm_tiling(m, d, f))


def _megablox_fwd(rows, w, group_sizes):
    return _megablox(rows, w, group_sizes), (rows, w, group_sizes)


def _megablox_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    rows, w, group_sizes = res
    (m, d), f = rows.shape, w.shape[-1]
    d_rows = gmm(g, w, group_sizes, rows.dtype, gmm_tiling(m, f, d),
                 transpose_rhs=True)
    d_w = tgmm(rows.swapaxes(0, 1), g, group_sizes, w.dtype,
               gmm_tiling(m, d, f), num_actual_groups=w.shape[0])
    return d_rows, d_w, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def grouped_matmul(rows: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """rows (M, d) sorted by group, w (H, d, f), group_sizes (E,) with
    H <= E -> (M, f): row r times the matrix of the group it lies in.
    ``w`` holds the first H of the E groups; rows of the other groups
    (they lie behind the held ones) cost no matmul and come out zero.

    On a TPU, where a tile divides the shapes (``gmm_tiling``): megablox's
    Pallas ``gmm`` (kernels ``gmm`` and, for the weights' gradient,
    ``tgmm``), which read the transposed weights in place for the rows'
    gradient.  Elsewhere ``jax.lax.ragged_dot``, which XLA lowers on a TPU
    to its own kernels (``ragged-dot*``): slower there by a fifth to a
    third in all three products, and its backward copies the weights
    transposed; with ``H < E`` its rows behind the held groups are set to
    zero here, which the TPU's kernel does not do itself.
    """
    (m, d), (held, _, f) = rows.shape, w.shape
    if jax.default_backend() == "tpu" and gmm_tiling(m, d, f):
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


def route_softmax(x: jax.Array, w_router: jax.Array, k: int):
    """-> (expert_idx (N, k), weights (N, k), logits, probs (N, E)): a
    float32 softmax over all E experts whose top-k probabilities weigh
    the experts' outputs as they are (not renormalised)."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)         # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)              # (N, k)
    return expert_idx, gate_vals, logits, probs


def route_sigmoid(x: jax.Array, w_router: jax.Array, select_bias: jax.Array,
                  k: int, weight_scale: float, eps: float = 1e-20):
    """-> (expert_idx (N, k), weights (N, k)): float32 sigmoid scores over
    all E experts; the k chosen are the top of score + ``select_bias``
    (the bias decides the choice and never a weight: it balances the load
    with no auxiliary loss and has no gradient), and their weights are
    the scores alone, renormalised over the k chosen and scaled.
    ``eps`` is what the family adds to the divisor (DeepSeek-V3's 1e-20,
    LFM2's 1e-6): part of its arithmetic, not a setting."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)         # (N, E)
    scores = jax.nn.sigmoid(logits)
    _, expert_idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), k)
    chosen = jnp.take_along_axis(scores, expert_idx, axis=-1)
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
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     *, num_experts: int, first_held: int = 0):
    """Each token through those of its chosen experts that are held here,
    weighted and summed -> (y (N, d), group_sizes (E,)).

    ``w_gate``, ``w_up`` (H, d, f) and ``w_down`` (H, f, d) are experts
    ``first_held .. first_held + H - 1`` of the ``num_experts`` the router
    chose among (all of them when H == E): the share of an expert-
    parallel layer that this chip holds.  The N k assignments are sorted
    by expert, the held experts' first, in one sort that returns the order
    and each row's weight with it (``_sort_by_expert``; the order sorted
    back is its inverse).  Both are permutations of the assignments
    because a sort over an iota made them, so the gathers that carry the
    rows to the experts and back promise their indices and nothing is
    filled, selected or scattered.  Three grouped matmuls run over the
    held groups, and the rows are put back, a token's k slots leading,
    and summed per token in float32.  The weight multiplies
    the hidden rows ``silu(gate) * up`` in float32, inside the fusion that
    makes them, and not the output rows: the down projection is linear,
    so ``down(w h) = w down(h)``, and the combine is then the dispatch
    transposed (``_experts_to_rows``), whose gradient reads no output row.
    (A weight on the output rows has the gradient ``sum_d out * g``: a
    checkpointed layer would run the down projection a second time in
    its backward only to feed that product.)  A choice of an absent
    expert costs no matmul and adds nothing (its rows come out of
    ``grouped_matmul`` zero): what that expert would add is another
    chip's part, and on several chips the exchange of rows goes between
    the sort and the matmuls (DESIGN.md, held experts).  No capacity: no
    (N, E, C) tensor exists and nothing is dropped among the held,
    whatever the imbalance.  ``group_sizes[i]`` counts the rows of expert
    ``first_held + i`` (mod E).
    """
    k = expert_idx.shape[1]
    with jax.named_scope("moe_dispatch"):
        order, inverse, w_sorted, group_sizes = _sorted_assignments(
            expert_idx, weights, num_experts, first_held)
        rows = _rows_to_experts(x, order, inverse)               # (N k, d)
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, w_gate.astype(x.dtype), group_sizes)
        up = grouped_matmul(rows, w_up.astype(x.dtype), group_sizes)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32) * w_sorted[:, None])
        out = grouped_matmul(hidden.astype(x.dtype), w_down.astype(x.dtype),
                             group_sizes)
    with jax.named_scope("moe_combine"):
        y = _experts_to_rows(out, order, inverse, k)
    return y, group_sizes


def dropless_moe_ffn(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
                     w_up: jax.Array, w_down: jax.Array, *, k: int,
                     scoring: str = "softmax",
                     select_bias: Optional[jax.Array] = None,
                     weight_scale: float = 1.0, first_held: int = 0,
                     choices: bool = False,
                     live: Optional[jax.Array] = None):
    """Token-choice SwiGLU experts with no capacity: every token is
    computed by each of its top-k experts that is held here, whatever the
    imbalance.

    x (N, d); w_router (d, E): the router always has its full width.
    w_gate, w_up (H, d, f); w_down (H, f, d): the H <= E experts held
    here, ``first_held`` the first (``dropless_experts``).  ``scoring``
    ``"softmax"`` (``route_softmax``) returns ``RouterStats`` over all E;
    ``"sigmoid"`` (``route_sigmoid`` with ``select_bias`` (E,) and
    ``weight_scale``) has no auxiliary term and returns ``HeldStats``.
    ``choices`` (a serving step): a third result, the expert ids (N, k)
    int32 that made ``y``; ``live`` (N,) bool with it: the rows that are
    some sequence's (``choice_of_live_rows``).
    """
    n = x.shape[0]
    num_experts, held = w_router.shape[-1], w_gate.shape[0]
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown scoring {scoring!r} "
                         "(expected softmax | sigmoid)")
    with jax.named_scope("router"):
        if scoring == "softmax":
            expert_idx, weights, logits, probs = route_softmax(x, w_router, k)
        else:
            expert_idx, weights = route_sigmoid(x, w_router, select_bias, k,
                                                weight_scale)
    if live is not None:
        expert_idx = choice_of_live_rows(expert_idx, live)
    y, group_sizes = dropless_experts(
        x, expert_idx, weights, w_gate, w_up, w_down,
        num_experts=num_experts, first_held=first_held)
    chose = (expert_idx.astype(jnp.int32),) if choices else ()
    with jax.named_scope("router"):
        if scoring == "sigmoid":
            mine = group_sizes[:held].astype(jnp.float32)
            rows = mine.sum()
            return (y, HeldStats(rows, mine.max() / jnp.maximum(mine.mean(),
                                                                1e-9),
                                 rows / (n * k)), *chose)
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
