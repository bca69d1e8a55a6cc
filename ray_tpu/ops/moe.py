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
own matmuls; the sort costs a few passes over the rows.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, NamedTuple, Tuple

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


@jax.custom_vjp
def _rows_to_experts(x, order, inverse):
    """(N, d) tokens -> (N k, d) rows grouped by expert: row r holds token
    ``order[r] // k``.  ``order`` is a permutation of the N k (token, slot)
    assignments and ``inverse`` its inverse, so the backward is a gather
    and a sum over a token's k slots, not the scatter-add XLA would
    derive from the forward gather."""
    k = order.shape[0] // x.shape[0]
    return jnp.take(x, order // k, axis=0)


def _rows_to_experts_fwd(x, order, inverse):
    return _rows_to_experts(x, order, inverse), (x.shape[0], inverse)


def _rows_to_experts_bwd(res, g):
    n, inverse = res
    dx = jnp.take(g, inverse, axis=0).reshape(n, -1, g.shape[-1])
    return dx.sum(1, dtype=jnp.float32).astype(g.dtype), None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inverse):
    """``y[perm]`` for a permutation whose inverse is known: the backward
    is ``g[inverse]``."""
    return jnp.take(y, perm, axis=0)


def _permute_rows_fwd(y, perm, inverse):
    return jnp.take(y, perm, axis=0), (perm, inverse)


def _permute_rows_bwd(res, g):
    perm, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


# megablox's (rows, contracted, output) tile: the fastest of those tried on
# the v5e at the OLMoE cell's shape that fits VMEM (PERF.md section 6, PR 27)
GMM_TILING = (512, 1024, 1024)


def grouped_matmul(rows: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """rows (M, d) sorted by group, w (E, d, f), group_sizes (E,) summing
    to M -> (M, f): row r times the matrix of the group it lies in.

    On a TPU, where the tile divides the shapes: megablox's Pallas ``gmm``
    (kernels ``gmm`` and, for the weights' gradient, ``tgmm``), which read
    the transposed weights in place for the rows' gradient.  Elsewhere
    ``jax.lax.ragged_dot``, which XLA lowers on a TPU to its own kernels
    (``ragged-dot*``): slower there by a fifth to a third in all three
    products, and its backward copies the weights transposed.
    """
    m, d = rows.shape
    tm, tk, tn = GMM_TILING
    f = w.shape[-1]
    if jax.default_backend() == "tpu" and not (m % tm or d % tk or f % tn):
        from jax.experimental.pallas.ops.tpu.megablox import ops
        return ops.gmm(rows, w, group_sizes, rows.dtype, GMM_TILING)
    return jax.lax.ragged_dot(rows, w, group_sizes)


def dropless_moe_ffn(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
                     w_up: jax.Array, w_down: jax.Array, *, k: int
                     ) -> Tuple[jax.Array, RouterStats]:
    """Token-choice SwiGLU experts with no capacity: every token is
    computed by each of its top-k experts, whatever the imbalance.

    x (N, d); w_router (d, E); w_gate, w_up (E, d, f); w_down (E, f, d).
    The router's softmax is float32 over all E experts and its top-k
    probabilities weigh the experts' outputs as they are (not
    renormalised).  Dispatch is a sort: the N k assignments are ordered by
    expert, the rows gathered, three grouped matmuls run over the ragged
    groups, and the rows are put back and summed per token.  No (N, E, C)
    tensor exists and nothing is dropped by construction.
    """
    n, d = x.shape
    num_experts = w_router.shape[-1]
    with jax.named_scope("router"):
        logits = jnp.dot(x, w_router.astype(x.dtype),
                         preferred_element_type=jnp.float32)     # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (N, k)
    with jax.named_scope("moe_dispatch"):
        flat_expert = expert_idx.reshape(n * k)
        order = jnp.argsort(flat_expert, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.bincount(flat_expert, length=num_experts
                                   ).astype(jnp.int32)
        rows = _rows_to_experts(x, order, inverse)               # (N k, d)
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, w_gate.astype(x.dtype), group_sizes)
        up = grouped_matmul(rows, w_up.astype(x.dtype), group_sizes)
        out = grouped_matmul(jax.nn.silu(gate) * up,
                             w_down.astype(x.dtype), group_sizes)
    with jax.named_scope("moe_combine"):
        out = _permute_rows(out, inverse, order).reshape(n, k, d)
        y = jnp.einsum("nkd,nk->nd", out, gate_vals.astype(out.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    with jax.named_scope("router"):
        share = group_sizes.astype(jnp.float32) / (n * k)        # f_e
        balance = num_experts * jnp.sum(share * probs.mean(0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        load = share.max() * num_experts
    return y, RouterStats(balance, z, load)


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
