"""LFM2-MoE: gated short convolutions beside grouped-query attention, and
sigmoid-routed experts behind leading dense layers (Liquid AI;
``model_type`` ``lfm2_moe``, ``transformers``' ``modeling_lfm2_moe.py``).

One block, its mixer and its feed-forward each of one of two kinds::

    h = x + mixer(RMSNorm_operator(x))
    y = h + ffn(RMSNorm_ffn(h))

    conv mixer       [B | C | x] = W_in u;  z = B * x;
                     c_t = sum_j w[j] * z_{t-(K-1)+j}   (depthwise, causal,
                     no bias, no activation);  out = W_out (C * c)
    attention mixer  q, k, v projections (H / KV / KV heads of D); RMSNorm
                     over each head's D dimensions of q and of k, BEFORE
                     RoPE (rotate-half over all D); causal softmax at
                     1 / sqrt(D), a KV head serving H / KV query heads; W_o
    dense ffn        W_2 (silu(W_1 h) * W_3 h)
    routed ffn       s = sigmoid(W_g h) in float32; the k chosen are the
                     top of s + expert_bias; their weights are s of the
                     chosen over (their sum + 1e-6), times the scaling
                     factor; sum_e w_e Expert_e(h), each a SwiGLU.  No
                     shared expert.

After the last layer ``embedding_norm`` (RMSNorm), then the head, which is
the embedding transposed (tied).  No bias anywhere.

Layers of four kinds in one model.  ``layer_types`` says which mixer each
layer has; the first ``n_dense_layer`` layers have the dense ffn and every
later one the experts.  The dense layers' leaves lie apart, one tree a
layer (``params["dense"]``, applied one by one); the routed layers repeat
one *period* of mixers (published: attention, conv, conv, conv), and
``lax.scan`` runs over whole periods, the period's layers unrolled inside
its body, each position's leaves stacked on a leading axis of periods
(``params["periods"]["p<j>"]``): as ``models/deepseek_v3.py`` scans its
dense and sparse stacks apart.  Where the routed layers end inside a
period (the published 40 do: nine periods and half of one), the rest lie
apart like the dense ones (``params["tail"]``).

Serving.  A sequence holds K/V in the attention layers and, in the conv
layers, the conv's *tail*: the last ``K - 1`` products ``z``, float32.  No
layer holds both, so the cache is told two counts (:func:`cache_layers`):
the K/V pool is laid out for the attention layers alone, numbered in layer
order, and the store of state (:func:`recurrent_state`) for the conv
layers alone.  ``forward_prefill`` returns K/V ``(kv layers, B, T, KV, D)``
and the tails at the prompt's last real position ``(state layers, B, K-1,
E)``; ``forward_decode`` reads the pool by an attention layer's number
among its kind and steps the store's rows that ``rows`` names, gathered
and scattered (a layer's rows are 0.5 MB at the published width).  Asked
(``choices=True``), both also return the experts each routed layer chose,
int32 ``(routed layers, rows, k)`` in layer order: what
:func:`routed_layers` promises the serving runner
(``serve/llm/model_runner.py``, the hand-over).

Random weights.  Every matrix is drawn at ``1 / sqrt(fan_in)`` (unit
output variance for unit-variance input), the experts' ``w2`` times
``sqrt(k)`` so that k experts weighed about ``1 / k`` each add what the
dense ffn adds; the embedding at ``1 / sqrt(E)``, so that the tied head
gives logits a standard deviation near 1; ``expert_bias`` at 0.01: small
beside the scores' spread and not zero, so that a choice it makes differs
from the scores' own.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models._common import (  # noqa: F401
    _gqa_expand, _rms_norm, _rope, _rope_at, normal_init, param_count)
from ray_tpu.ops import short_conv

Params = Dict[str, Any]

CONV, ATTN = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    max_positions: int = 128000
    n_embd: int = 2048
    n_layer: int = 40
    n_dense_layer: int = 2           # num_dense_layers: they lead
    # each layer's mixer, as published: (conv, conv, full_attention, conv)
    # repeated
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTN, CONV) * 10
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    ffn_dim: int = 11776             # the dense layers' SwiGLU
    expert_dim: int = 1536           # moe_intermediate_size
    n_experts: int = 64
    experts_per_token: int = 4
    routed_scale: float = 1.0        # routed_scaling_factor
    conv_width: int = 3              # conv_L_cache
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # what init_params draws the matrices in; the leaves of WIDE_PARAMS
    # are float32 whatever this says
    param_dtype: Any = jnp.float32
    # ops.attention.causal_attention chooses by it (prefill)
    attn_impl: str = "auto"

    def __post_init__(self):
        if len(self.layer_types) != self.n_layer \
                or set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(
                f"layer_types must name {self.n_layer} mixers, each "
                f"{CONV!r} or {ATTN!r}; got {self.layer_types}")
        if not 0 <= self.n_dense_layer < self.n_layer:
            raise ValueError(f"{self.n_dense_layer} dense layers of "
                             f"{self.n_layer}: at least one layer routes")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern of mixers that the routed layers repeat
        (the last repeat may be cut short)."""
        routed = self.layer_types[self.n_dense_layer:]
        for p in range(1, len(routed) + 1):
            if all(routed[i] == routed[i % p] for i in range(len(routed))):
                return routed[:p]
        return routed

    @property
    def n_period(self) -> int:
        """Whole periods among the routed layers: what the scan runs."""
        return (self.n_layer - self.n_dense_layer) // len(self.period)

    @property
    def tail_types(self) -> Tuple[str, ...]:
        """The mixers of the routed layers behind the last whole period."""
        return self.layer_types[self.n_dense_layer
                                + self.n_period * len(self.period):]

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)


def lfm2_24b_a2b_l9() -> Lfm2Config:
    """LFM2-24B-A2B's published widths at layers 1-9 of its 40: one of the
    two leading dense layers (a conv layer) and two whole periods of the
    routed layers behind them, in the type it is served in
    (``perfbench/configs/lfm2-24b-a2b.json``)."""
    return Lfm2Config(n_layer=9, n_dense_layer=1,
                      layer_types=(CONV,) + (ATTN, CONV, CONV, CONV) * 2,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def tiny(vocab: int = 128, seq: int = 128) -> Lfm2Config:
    """Two periods behind one dense layer at a test's size: heads x
    head_dim != n_embd, 2 query heads a KV head, 8 experts, 2 a token."""
    return Lfm2Config(
        vocab_size=vocab, max_positions=seq, n_embd=64, n_layer=9,
        n_dense_layer=1, layer_types=(CONV,) + (ATTN, CONV, CONV, CONV) * 2,
        n_head=4, n_kv_head=2, head_dim=8, ffn_dim=96, expert_dim=48,
        n_experts=8, experts_per_token=2)


PRESETS = {"lfm2-24b-a2b-l9": lfm2_24b_a2b_l9, "tiny": tiny}

# Used as stored (float32): the norms' scales, multiplied in float32 by
# _rms_norm, and the router's selection bias, added to float32 scores.
# Every other leaf is cast to cfg.dtype at its use.
WIDE_PARAMS = ("operator_norm", "ffn_norm", "q_norm", "k_norm",
               "embedding_norm", "expert_bias")


# ------------------------------------------------------------------- params
def _layer_params(key: jax.Array, cfg: Lfm2Config, kind: str, routed: bool,
                  lead: Tuple[int, ...]) -> Params:
    """One layer's leaves, each with ``lead`` in front of its shape (() for
    a dense layer, (periods,) for a position of the period)."""
    pd, f32 = cfg.param_dtype, jnp.float32
    E, H, KV, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    k = iter(jax.random.split(key, 12))

    def matrix(*shape, fan_in: int, gain: float = 1.0):
        return normal_init(next(k), (*lead, *shape), pd,
                           gain / math.sqrt(fan_in))

    lp = {"operator_norm": {"scale": jnp.ones((*lead, E), f32)},
          "ffn_norm": {"scale": jnp.ones((*lead, E), f32)}}
    if kind == CONV:
        lp["conv_in"] = {"kernel": matrix(E, 3 * E, fan_in=E)}
        # (K, E): index K-1 takes the current token (the published conv1d
        # weight is its transpose, (E, 1, K))
        lp["conv"] = {"kernel": matrix(cfg.conv_width, E,
                                       fan_in=cfg.conv_width)}
        lp["conv_out"] = {"kernel": matrix(E, E, fan_in=E)}
    else:
        lp["wq"] = {"kernel": matrix(E, H * D, fan_in=E)}
        lp["wk"] = {"kernel": matrix(E, KV * D, fan_in=E)}
        lp["wv"] = {"kernel": matrix(E, KV * D, fan_in=E)}
        lp["q_norm"] = {"scale": jnp.ones((*lead, D), f32)}
        lp["k_norm"] = {"scale": jnp.ones((*lead, D), f32)}
        lp["wo"] = {"kernel": matrix(H * D, E, fan_in=H * D)}
    if routed:
        X, F = cfg.n_experts, cfg.expert_dim
        lp["router"] = {"kernel": matrix(E, X, fan_in=E)}
        lp["expert_bias"] = jax.random.normal(next(k), (*lead, X), f32) * 0.01
        lp["experts"] = {
            "w1": matrix(X, E, F, fan_in=E), "w3": matrix(X, E, F, fan_in=E),
            "w2": matrix(X, F, E, fan_in=F,
                         gain=math.sqrt(cfg.experts_per_token))}
    else:
        F = cfg.ffn_dim
        lp["w1"] = {"kernel": matrix(E, F, fan_in=E)}
        lp["w3"] = {"kernel": matrix(E, F, fan_in=E)}
        lp["w2"] = {"kernel": matrix(F, E, fan_in=F)}
    return lp


def init_params(rng: jax.Array, cfg: Lfm2Config) -> Params:
    """``dense``: one tree a leading dense layer (``d<i>``); ``periods``:
    one tree a position of the period (``p<j>``), its leaves stacked on a
    leading axis of periods; ``tail``: one tree a routed layer behind the
    last whole period (``t<i>``); the head is ``wte`` transposed."""
    keys = iter(jax.random.split(rng, 1 + cfg.n_dense_layer
                                 + len(cfg.period) + len(cfg.tail_types)))
    return {
        "wte": normal_init(next(keys), (cfg.vocab_size, cfg.n_embd),
                           cfg.param_dtype, 1.0 / math.sqrt(cfg.n_embd)),
        "dense": {f"d{i}": _layer_params(next(keys), cfg, cfg.layer_types[i],
                                         False, ())
                  for i in range(cfg.n_dense_layer)},
        "periods": {f"p{j}": _layer_params(next(keys), cfg, kind, True,
                                           (cfg.n_period,))
                    for j, kind in enumerate(cfg.period)},
        "tail": {f"t{i}": _layer_params(next(keys), cfg, kind, True, ())
                 for i, kind in enumerate(cfg.tail_types)},
        "embedding_norm": {"scale": jnp.ones((cfg.n_embd,), jnp.float32)},
    }


def recurrent_state(cfg: Lfm2Config) -> Dict[str, jax.ShapeDtypeStruct]:
    """One sequence's recurrent state in one CONV layer: the conv's tail.
    What the serving cache keeps a row of per sequence and state layer (a
    store leaf is ``(cache_layers(cfg)["state"], rows, *shape)``)."""
    return {"conv": jax.ShapeDtypeStruct((cfg.conv_width - 1, cfg.n_embd),
                                         jnp.float32)}


def cache_layers(cfg: Lfm2Config) -> Dict[str, int]:
    """How many layers hold K/V and how many hold recurrent state: here
    no layer holds both, so neither count is ``n_layer``.  Each kind is
    numbered in layer order among its own."""
    return {"kv": cfg.count(ATTN), "state": cfg.count(CONV)}


def routed_layers(cfg: Lfm2Config) -> Dict[str, int]:
    """What the step programs hand over beside the logits: the expert ids
    each routed layer chose, int32 (layers, rows, k), in layer order."""
    return {"layers": cfg.n_layer - cfg.n_dense_layer,
            "k": cfg.experts_per_token}


# ------------------------------------------------------------------ pieces
# The layer kinds shared with the other decoders run under their
# ``jax.named_scope`` names (embed, ln_1, attn_qkv, qk_norm, rope, attn /
# paged_attention, attn_out, ln_2, mlp, ln_f, lm_head; the moe_* of
# ops/moe.py), the conv mixer's under conv_in, conv_scan (a sequence) or
# conv_step (a token) and conv_out.  Metadata only: PERF.md section 3
# lists the metric that reads each.
def _embed(params: Params, tokens: jax.Array, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("embed"):
        return params["wte"].astype(cfg.dtype)[tokens]


def _logits(params: Params, x: jax.Array, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("ln_f"):
        x = _rms_norm(x, params["embedding_norm"]["scale"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        return lax.dot_general(
            x, params["wte"].astype(cfg.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _qkv(u: jax.Array, lp: Params, cfg: Lfm2Config):
    """Normed hidden states (..., E) -> q (..., H, D), k, v (..., KV, D),
    q and k normed a head, before RoPE."""
    H, D, KV = cfg.n_head, cfg.head_dim, cfg.n_kv_head
    lead = u.shape[:-1]
    with jax.named_scope("attn_qkv"):
        q = (u @ lp["wq"]["kernel"].astype(cfg.dtype)).reshape(*lead, H, D)
        k = (u @ lp["wk"]["kernel"].astype(cfg.dtype)).reshape(*lead, KV, D)
        v = (u @ lp["wv"]["kernel"].astype(cfg.dtype)).reshape(*lead, KV, D)
    with jax.named_scope("qk_norm"):
        q = _rms_norm(q, lp["q_norm"]["scale"], cfg.rms_eps)
        k = _rms_norm(k, lp["k_norm"]["scale"], cfg.rms_eps)
    return q, k, v


def _attn_out(a: jax.Array, lp: Params, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("attn_out"):
        return a.reshape(*a.shape[:-2], cfg.n_head * cfg.head_dim) \
            @ lp["wo"]["kernel"].astype(cfg.dtype)


def _conv_in(u: jax.Array, lp: Params, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("conv_in"):
        return u @ lp["conv_in"]["kernel"].astype(cfg.dtype)


def _conv_out(g: jax.Array, lp: Params, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("conv_out"):
        return g.astype(cfg.dtype) @ lp["conv_out"]["kernel"].astype(cfg.dtype)


def _mlp(h: jax.Array, lp: Params, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(h @ lp["w1"]["kernel"].astype(cfg.dtype))
        up = h @ lp["w3"]["kernel"].astype(cfg.dtype)
        return (gate * up) @ lp["w2"]["kernel"].astype(cfg.dtype)


def _split_experts(periods: Params):
    """A period's stacks -> (what the layer scan slices a period of: every
    leaf but the experts'; the experts' leaves whole, ``(periods x
    experts, ...)``, by position of the period).

    The grouped matmuls are kernels, and a kernel's operand is a buffer:
    a period's experts sliced out of their stack by the scan would be
    copied whole for each (12 x 0.4 GB a period at the published widths,
    more than the step reads; seen in the program compiled for the v5e,
    PERF.md PR 39).  So the experts stay outside the scan's slices: every
    period's experts of a position are ONE run of groups, a routed layer
    names its own as groups ``period x X .. period x X + X - 1``, and the
    other periods' groups are empty: 0.04 us each in a call of 128 rows on
    the v5e, 0.5% of a call at 2 periods and 5.6% at the published 9.5
    (PERF.md PR 39)."""
    sliced = {pos: {k: v for k, v in lp.items() if k != "experts"}
              for pos, lp in periods.items()}
    whole = {pos: {k: w.reshape(-1, *w.shape[2:])
                   for k, w in lp["experts"].items()}
             for pos, lp in periods.items()}
    return sliced, whole


def _experts(h: jax.Array, lp: Params, ex: Params, period, cfg: Lfm2Config,
             live: Optional[jax.Array] = None):
    """The routed ffn on normed hidden states (..., E) -> (out, the
    chosen expert ids (rows, k) int32).  ``ex``: the experts of this
    position of EVERY period (:func:`_split_experts`); ``period`` (traced)
    says which run of them is this layer's; ``live`` (rows,) bool: a
    decode step's rows that are some sequence's (the padded ones then
    read no expert of their own: ``ops/moe.choice_of_live_rows``)."""
    from ray_tpu.ops.moe import choice_of_live_rows, dropless_experts, \
        route_sigmoid
    x = h.reshape(-1, h.shape[-1])
    with jax.named_scope("moe_router"):
        idx, weights = route_sigmoid(
            x, lp["router"]["kernel"], lp["expert_bias"],
            cfg.experts_per_token, cfg.routed_scale, eps=1e-6)
    if live is not None:
        idx = choice_of_live_rows(idx, live)
    y, _ = dropless_experts(
        x, idx + period * cfg.n_experts, weights, ex["w1"], ex["w3"],
        ex["w2"], num_experts=ex["w1"].shape[0])
    return y.reshape(h.shape), idx.astype(jnp.int32)


def _ffn(h: jax.Array, lp: Params, cfg: Lfm2Config, ex: Optional[Params],
         period=0, live: Optional[jax.Array] = None):
    """x + ffn(RMSNorm_ffn(x)) -> (out, the chosen ids or None); ``ex``
    None: the layer is dense."""
    with jax.named_scope("ln_2"):
        n = _rms_norm(h, lp["ffn_norm"]["scale"], cfg.rms_eps)
    if ex is None:
        return h + _mlp(n, lp, cfg), None
    out, idx = _experts(n, lp, ex, period, cfg, live)
    return h + out, idx


def _operator_norm(x: jax.Array, lp: Params, cfg: Lfm2Config) -> jax.Array:
    with jax.named_scope("ln_1"):
        return _rms_norm(x, lp["operator_norm"]["scale"], cfg.rms_eps)


def _stacked(per_period: List[jax.Array]) -> jax.Array:
    """A scan's results, one ``(periods, ...)`` array a position of the
    period, as one array in layer order ``(periods x positions, ...)``."""
    a = jnp.stack(per_period, axis=1)
    return a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])


def _in_layer_order(dense: list, scanned: list,
                    tail: list) -> Optional[jax.Array]:
    """What the layers of one kind produced, ``(layers of the kind,
    ...)``: the dense layers' first, then the periods', then the tail's."""
    parts = [jnp.stack(dense)] if dense else []
    if scanned:
        parts.append(_stacked(scanned))
    if tail:
        parts.append(jnp.stack(tail))
    return jnp.concatenate(parts) if parts else None


# ------------------------------------------------------------------ forward
def forward_prefill(params: Params, tokens: jax.Array, cfg: Lfm2Config,
                    last_pos: Optional[jax.Array] = None,
                    choices: bool = False):
    """tokens (B, T) -> (logits, k, v, state): k / v (kv layers, B, T, KV,
    D) as llama.forward_prefill caches them (keys post-RoPE, values before
    the groups are expanded); ``state`` the conv tails at ``last_pos``,
    ``{"conv": (state layers, B, K-1, E)}``, or None without it.  With
    ``choices`` a fifth result: the experts chosen, (routed layers, B x T,
    k) int32.

    ``last_pos`` (traced scalar): logits only at that position, (B, V);
    None returns all of them, (B, T, V)."""
    from ray_tpu.ops.attention import causal_attention
    H = cfg.n_head

    def layer(x, lp, kind, ex=None, period=0):
        """-> (out, (k, v) or None, tail or None, ids or None)."""
        u = _operator_norm(x, lp, cfg)
        kv = tail = None
        if kind == CONV:
            with jax.named_scope("conv_scan"):
                g, tail = short_conv.gated_conv(
                    _conv_in(u, lp, cfg), lp["conv"]["kernel"], last_pos)
            h = x + _conv_out(g, lp, cfg)
        else:
            q, k, v = _qkv(u, lp, cfg)
            with jax.named_scope("rope"):
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
            with jax.named_scope("attn"):
                a = causal_attention(q, _gqa_expand(k, H), _gqa_expand(v, H),
                                     impl=cfg.attn_impl)
            h, kv = x + _attn_out(a, lp, cfg), (k, v)
        out, ids = _ffn(h, lp, cfg, ex, period)
        return out, kv, tail, ids

    x = _embed(params, tokens, cfg)
    dense_kv, dense_tails = [], []
    for i in range(cfg.n_dense_layer):
        x, kv, tail, _ = layer(x, params["dense"][f"d{i}"],
                               cfg.layer_types[i])
        if kv is not None:
            dense_kv.append(kv)
        if tail is not None:
            dense_tails.append(tail)
    sliced, experts = _split_experts(params["periods"])

    def body(x, xs):
        period, i = xs
        kvs, tails, ids = [], [], []
        for j, kind in enumerate(cfg.period):
            x, kv, tail, chose = layer(x, period[f"p{j}"], kind,
                                       experts[f"p{j}"], i)
            ids.append(chose)
            if kv is not None:
                kvs.append(kv)
            if tail is not None:
                tails.append(tail)
        return x, (kvs, tails, ids)

    x, (kvs, tails, ids) = lax.scan(body, x,
                                    (sliced, jnp.arange(cfg.n_period)))
    tail_kv, tail_tails, tail_ids = [], [], []
    for i, kind in enumerate(cfg.tail_types):
        lp = params["tail"][f"t{i}"]
        x, kv, tail, chose = layer(x, lp, kind, lp["experts"])
        tail_ids.append(chose)
        if kv is not None:
            tail_kv.append(kv)
        if tail is not None:
            tail_tails.append(tail)
    ks = _in_layer_order([k for k, _ in dense_kv], [k for k, _ in kvs],
                         [k for k, _ in tail_kv])
    vs = _in_layer_order([v for _, v in dense_kv], [v for _, v in kvs],
                         [v for _, v in tail_kv])
    state = None
    if last_pos is not None:
        state = {"conv": _in_layer_order(dense_tails, tails, tail_tails)}
        x = lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)[:, 0]
    out = (_logits(params, x, cfg), ks, vs, state)
    return (*out, _in_layer_order([], ids, tail_ids)) if choices else out


def forward(params: Params, tokens: jax.Array, cfg: Lfm2Config) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab) float32."""
    return forward_prefill(params, tokens, cfg)[0]


def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array, cfg: Lfm2Config,
                   state: Dict[str, jax.Array], rows: jax.Array,
                   choices: bool = False,
                   live: Optional[jax.Array] = None):
    """One decode step over the engine's paged K/V pool (read-only here,
    its leading axis the attention layers) and its store of conv tails
    (leading axis the conv layers).

    ``state``: ``{"conv": (state layers, R, K-1, E)}``; ``rows`` (B,) the
    store row of each batch row (one outside the store has none: it reads
    any and writes nowhere).  Returns (logits (B, V) f32, new_k, new_v
    (kv layers, B, KV, D), the store with the named rows stepped) and,
    with ``choices``, the experts chosen, (routed layers, B, k) int32.
    ``live`` (B,) bool: the rows that are not padding up to the bucket,
    for the routing (:func:`_experts`)."""
    from ray_tpu.ops.paged_attention import paged_attention_decode
    store = state["conv"]
    read_rows = jnp.minimum(rows, store.shape[1] - 1)

    def layer(x, store, lp, kind, number, ex=None, period=0):
        """``number``: the layer's among its kind, where it reads the
        pool or the store.  -> (out, store, (k, v) or None, ids)."""
        u = _operator_norm(x, lp, cfg)
        kv = None
        if kind == CONV:
            p = _conv_in(u, lp, cfg)
            with jax.named_scope("conv_step"):
                mine = lax.dynamic_index_in_dim(store, number, 0,
                                                keepdims=False)
                g, tails = short_conv.gated_conv_step(
                    mine[read_rows], p, lp["conv"]["kernel"])
                store = lax.dynamic_update_index_in_dim(
                    store, mine.at[rows].set(tails, mode="drop"), number, 0)
            h = x + _conv_out(g, lp, cfg)
        else:
            q, k, v = _qkv(u, lp, cfg)
            with jax.named_scope("rope"):
                q = _rope_at(q, positions, cfg.rope_theta)
                k = _rope_at(k, positions, cfg.rope_theta)
            a = paged_attention_decode(q, kv_pool, number, block_tables,
                                       ctx_lens, k, v)
            h, kv = x + _attn_out(a, lp, cfg), (k, v)
        out, ids = _ffn(h, lp, cfg, ex, period, live)
        return out, store, kv, ids

    x = _embed(params, tokens, cfg)
    seen = {CONV: 0, ATTN: 0}        # layers of each kind so far
    dense_kv = []
    for i in range(cfg.n_dense_layer):
        kind = cfg.layer_types[i]
        x, store, kv, _ = layer(x, store, params["dense"][f"d{i}"], kind,
                                seen[kind])
        seen[kind] += 1
        if kv is not None:
            dense_kv.append(kv)
    per = {kind: cfg.period.count(kind) for kind in (CONV, ATTN)}
    sliced, experts = _split_experts(params["periods"])

    def body(carry, xs):
        x, store = carry
        period, i = xs
        at = {kind: seen[kind] + i * per[kind] for kind in per}
        kvs, ids = [], []
        for j, kind in enumerate(cfg.period):
            x, store, kv, chose = layer(x, store, period[f"p{j}"], kind,
                                        at[kind], experts[f"p{j}"], i)
            at[kind] = at[kind] + 1
            ids.append(chose)
            if kv is not None:
                kvs.append(kv)
        return (x, store), (kvs, ids)

    (x, store), (kvs, ids) = lax.scan(
        body, (x, store), (sliced, jnp.arange(cfg.n_period)))
    seen = {kind: seen[kind] + cfg.n_period * per[kind] for kind in per}
    tail_kv, tail_ids = [], []
    for i, kind in enumerate(cfg.tail_types):
        lp = params["tail"][f"t{i}"]
        x, store, kv, chose = layer(x, store, lp, kind, seen[kind],
                                    lp["experts"])
        seen[kind] += 1
        tail_ids.append(chose)
        if kv is not None:
            tail_kv.append(kv)
    ks = _in_layer_order([k for k, _ in dense_kv], [k for k, _ in kvs],
                         [k for k, _ in tail_kv])
    vs = _in_layer_order([v for _, v in dense_kv], [v for _, v in kvs],
                         [v for _, v in tail_kv])
    out = (_logits(params, x, cfg), ks, vs, {"conv": store})
    return (*out, _in_layer_order([], ids, tail_ids)) if choices else out
