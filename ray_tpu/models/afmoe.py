"""AFMoE (Arcee Trinity; ``model_type`` ``afmoe``): sliding-window and full
attention mixed three to one, the attention's output gated, sandwich norms,
and sigmoid-routed experts beside a shared one behind leading dense layers.

``x`` is a position's stream of ``E``.  ``x_0 = wte[token] * sqrt(E)``
(``mup_enabled``); every layer::

    x = x + N2(Attn(N1(x)))
    x = x + N4(F(N3(x)))

four RMSNorms a layer; after the last a final RMSNorm and the untied head.
No bias anywhere.

    Attn(u)   q = W_q u (H heads of D), k = W_k u, v = W_v u (KV heads),
              g = W_g u (H x D); RMSNorm a head on q and k (a learned
              D-scale each); on SLIDING layers only rotate-half RoPE over
              all D, and only there a query at t sees the keys
              ``t - window + 1 .. t`` (a FULL layer: no RoPE, every key up
              to t); softmax(q k^T / sqrt(D)) in float32;
              o = (softmax v) * sigmoid(g);  out = W_o o
    F dense   W_2 (silu(W_1 h) * W_3 h)
    F routed  s = sigmoid(W_r h) in float32 over all ``n_experts``; the k
              chosen are the top of s + expert_bias (the bias decides the
              choice and never a weight); weights s_chosen / (sum s_chosen
              + 1e-20) * route_scale;  out = SwiGLU_shared(h) + the
              weighted sum of the chosen experts' SwiGLU(h)

A share of the experts.  ``held_experts`` of the ``n_experts`` the router
scores lie here, from ``first_held`` on: one chip's share of a layer that
several chips hold by its experts (``ops/moe.dropless_experts``).  What the
absent experts would add is another chip's part and is left out; the
shared expert is whole here.

Layers that differ in kind.  ``layer_types`` names each layer's attention;
the first ``n_dense_layer`` layers have the dense F.  The layers lie apart,
one tree each (``params["layers"]["l<i>"]``), applied one by one: there is
no scan over layers, so no scan slices a layer's experts out of a stack
(what ``lfm2._split_experts`` exists to avoid: a grouped matmul's operand
sliced by a scan is copied whole) and each layer's experts are a buffer of
their own.

Serving.  Both kinds of layer hold K/V, and hold it differently: a FULL
layer every position of the context, a SLIDING layer the last ``window``.
:func:`cache_layers` counts the two kinds; the cache keeps a pool and a
block table a kind (``serve/llm/kv_cache.py``, pages of two kinds), each
kind's layers numbered in layer order among their own, and gives a window
layer's blocks back as the context passes them.  ``forward_decode`` reads
the one pool by a full layer's number and the other, under the window, by a
sliding layer's.

A prompt in chunks.  :func:`forward_prefill_chunk` runs ``prefill_chunk``
positions of one prompt over a *staging* the caller keeps from chunk to
chunk (:func:`prefill_staging`): a full layer's holds every position of the
prompt so far, a sliding layer's is a ring of the window and a chunk
(``ops/window_attention.py``).  :func:`forward_prefill` is the same code
over a whole prompt, chunk by chunk.

Random weights.  Every matrix at ``1 / sqrt(fan_in)``; the embedding at
``1 / sqrt(E)``, so the stream's RMS is near 1 AFTER the ``sqrt(E)``
scale; the head at ``1 / sqrt(E)``; norm scales ones; ``expert_bias`` at
0.01: small beside the scores' spread and not zero.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models._common import (  # noqa: F401
    _rms_norm, _rope_at, normal_init, param_count)
from ray_tpu.ops import window_attention as wattn

Params = Dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"

# layer_types as published, 60 layers
PUBLISHED_LAYERS = (SLIDING, SLIDING, SLIDING, FULL) * 15


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    max_positions: int = 262144
    n_embd: int = 3072
    n_layer: int = 60
    n_dense_layer: int = 6           # num_dense_layers: they lead
    layer_types: Tuple[str, ...] = PUBLISHED_LAYERS
    n_head: int = 48
    n_kv_head: int = 8
    head_dim: int = 128
    ffn_dim: int = 12288             # the dense layers' SwiGLU
    expert_dim: int = 3072           # moe_intermediate_size
    n_experts: int = 256             # what the router scores
    experts_per_token: int = 4
    n_shared_experts: int = 1
    route_scale: float = 2.448
    # the experts held here, ``first_held .. first_held + held_experts - 1``
    # (0: all of them)
    held_experts: int = 0
    first_held: int = 0
    sliding_window: int = 4096
    rope_theta: float = 1e4          # the sliding layers' only
    rms_eps: float = 1e-5
    # positions one prefill program runs
    prefill_chunk: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.layer_types) != self.n_layer \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.n_layer} layers, each "
                f"{SLIDING!r} or {FULL!r}; got {self.layer_types}")
        if not 0 <= self.n_dense_layer <= self.n_layer:
            raise ValueError(f"{self.n_dense_layer} dense layers of "
                             f"{self.n_layer}")
        if not 0 <= self.first_held <= self.n_experts - self.held:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + self.held - 1}"
                f" are not among the router's {self.n_experts}")

    @property
    def held(self) -> int:
        return self.held_experts or self.n_experts

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)


def trinity_large_preview_l5() -> AfmoeConfig:
    """Trinity-Large-Preview's published widths at 5 of its 60 layers: one
    of the six leading dense layers (a sliding one) and four routed layers
    in the published order sliding, sliding, sliding, full (layers 8-11),
    32 of the 256 experts of each (one of 8 chips' share), 25,024 of the
    200,192 vocabulary rows, in the type it is served in
    (``perfbench/configs/trinity-large-preview.json``)."""
    return AfmoeConfig(
        vocab_size=25024, n_layer=5, n_dense_layer=1,
        layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
        held_experts=32, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def tiny(vocab: int = 128, first_held: int = 0) -> AfmoeConfig:
    """Five layers at a test's size, in the cut's order: a dense sliding
    layer, then sliding, sliding, sliding, full, routed; 3 query heads a KV
    head, a window of 48 positions, chunks of 32, 8 experts of which 4 are
    held, 2 a token."""
    return AfmoeConfig(
        vocab_size=vocab, max_positions=512, n_embd=64, n_layer=5,
        n_dense_layer=1,
        layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
        n_head=6, n_kv_head=2, head_dim=8, ffn_dim=96, expert_dim=48,
        n_experts=8, experts_per_token=2, held_experts=4,
        first_held=first_held, sliding_window=48, prefill_chunk=32,
        dtype=jnp.float32)


PRESETS = {"trinity-large-preview-l5": trinity_large_preview_l5,
           "tiny": tiny}

# Used as stored (float32): the norms' scales, multiplied in float32 by
# _rms_norm, and the router's selection bias, added to float32 scores.
# Every other leaf is cast to cfg.dtype at its use.
WIDE_PARAMS = ("norm1", "norm2", "norm3", "norm4", "q_norm", "k_norm",
               "ln_f", "expert_bias")


# ------------------------------------------------------------------- params
def _layer_params(key: jax.Array, cfg: AfmoeConfig, routed: bool) -> Params:
    pd, f32 = cfg.param_dtype, jnp.float32
    E, H, KV, D = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    k = iter(jax.random.split(key, 16))

    def matrix(*shape, fan_in: int):
        return normal_init(next(k), shape, pd, 1.0 / math.sqrt(fan_in))

    def swiglu(width: int, *lead: int):
        return {"w1": matrix(*lead, E, width, fan_in=E),
                "w3": matrix(*lead, E, width, fan_in=E),
                "w2": matrix(*lead, width, E, fan_in=width)}

    lp = {f"norm{i}": {"scale": jnp.ones((E,), f32)} for i in (1, 2, 3, 4)}
    lp.update(
        wq={"kernel": matrix(E, H * D, fan_in=E)},
        wk={"kernel": matrix(E, KV * D, fan_in=E)},
        wv={"kernel": matrix(E, KV * D, fan_in=E)},
        wg={"kernel": matrix(E, H * D, fan_in=E)},
        wo={"kernel": matrix(H * D, E, fan_in=H * D)},
        q_norm={"scale": jnp.ones((D,), f32)},
        k_norm={"scale": jnp.ones((D,), f32)})
    if not routed:
        lp["mlp"] = swiglu(cfg.ffn_dim)
        return lp
    lp["router"] = {"kernel": matrix(E, cfg.n_experts, fan_in=E)}
    lp["expert_bias"] = jax.random.normal(next(k), (cfg.n_experts,),
                                          f32) * 0.01
    lp["shared"] = swiglu(cfg.expert_dim * cfg.n_shared_experts)
    lp["experts"] = swiglu(cfg.expert_dim, cfg.held)
    return lp


def init_params(rng: jax.Array, cfg: AfmoeConfig) -> Params:
    """``layers``: one tree a layer (``l<i>``, two digits); the embedding
    is ``wte``, the head ``lm_head``.  A routed layer's ``experts`` are the
    ``held`` ones, ``first_held`` on; its router scores all of them."""
    keys = iter(jax.random.split(rng, cfg.n_layer + 2))
    E = cfg.n_embd
    return {
        "wte": normal_init(next(keys), (cfg.vocab_size, E), cfg.param_dtype,
                           1.0 / math.sqrt(E)),
        "layers": {f"l{i:02d}": _layer_params(next(keys), cfg,
                                              i >= cfg.n_dense_layer)
                   for i in range(cfg.n_layer)},
        "ln_f": {"scale": jnp.ones((E,), jnp.float32)},
        "lm_head": {"kernel": normal_init(
            next(keys), (E, cfg.vocab_size), cfg.param_dtype,
            1.0 / math.sqrt(E))},
    }


def cache_layers(cfg: AfmoeConfig) -> Dict[str, int]:
    """The layers by what they hold: ``kv``: K/V of every position (the
    full layers), ``window``: K/V of the last ``sliding_window`` positions
    (the sliding layers); none holds recurrent state.  Each kind is
    numbered in layer order among its own."""
    return {"kv": cfg.count(FULL), "window": cfg.count(SLIDING), "state": 0}


def routed_layers(cfg: AfmoeConfig) -> Optional[Dict[str, int]]:
    """What the step programs hand over beside the logits: the expert ids
    each routed layer chose (of all ``n_experts``, held or not), int32
    (layers, rows, k), in layer order; ``held`` (first, count) where the
    layer's experts here are a share of them."""
    if cfg.n_dense_layer == cfg.n_layer:
        return None
    out = {"layers": cfg.n_layer - cfg.n_dense_layer,
           "k": cfg.experts_per_token}
    if cfg.held < cfg.n_experts:
        # a share: the count of experts a step touched is of these
        out["held"] = (cfg.first_held, cfg.held)
    return out


def prefill_staging(cfg: AfmoeConfig,
                    positions: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """What a prompt's chunks keep between them: the full layers' K and V
    of ``positions`` positions (whole chunks), a position a row of ``KV x
    D`` lanes, and the sliding layers' ring of the window and a chunk."""
    f, c = cfg.n_kv_head * cfg.head_dim, cfg.prefill_chunk
    ring = wattn.ring_segments(cfg.sliding_window, c) * c
    full = jax.ShapeDtypeStruct((cfg.count(FULL), positions, f), jnp.float32)
    band = jax.ShapeDtypeStruct((cfg.count(SLIDING), ring, f), jnp.float32)
    return {"k": full, "v": full, "kw": band, "vw": band}


def staged_window(cfg: AfmoeConfig, staging: Dict[str, jax.Array], first,
                  positions: int) -> Tuple[jax.Array, jax.Array]:
    """The sliding layers' K and V of positions ``first .. first +
    positions - 1`` out of the ring, ``(sliding layers, positions, KV,
    D)``: what the cache scatters into a window layer's blocks when the
    prompt is done.  Positions the ring no longer holds (or never did) come
    out as whatever lies there; the caller names only those it holds."""
    c = cfg.prefill_chunk
    n = wattn.ring_segments(cfg.sliding_window, c)
    at = first + jnp.arange(positions)
    rows = (at // c) % n * c + at % c
    return tuple(
        staging[name][:, rows].reshape(-1, positions, cfg.n_kv_head,
                                       cfg.head_dim) for name in ("kw", "vw"))


# ------------------------------------------------------------------ pieces
# Scopes shared with the other decoders (embed, ln_1, attn_qkv, qk_norm,
# rope, attn_out, ln_2, mlp, ln_f, lm_head; the moe_* of ops/moe.py) and
# this family's own: attn_window / attn_full around a sliding / a full
# layer's attention (a prefill's chunk kernel, a decode's paged walk),
# attn_gate around the output gate, moe_shared around the shared expert,
# kv_stage around a chunk's writes to the staging.  Metadata only: PERF.md
# section 3 lists the metric that reads each.
def _w(lp: Params, name: str, cfg: AfmoeConfig) -> jax.Array:
    return lp[name]["kernel"].astype(cfg.dtype)


def _embed(params: Params, tokens: jax.Array, cfg: AfmoeConfig) -> jax.Array:
    with jax.named_scope("embed"):
        rows = params["wte"].astype(cfg.dtype)[tokens].astype(jnp.float32)
        return (rows * math.sqrt(cfg.n_embd)).astype(cfg.dtype)


def _logits(params: Params, x: jax.Array, cfg: AfmoeConfig) -> jax.Array:
    with jax.named_scope("ln_f"):
        x = _rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        return jnp.dot(x, params["lm_head"]["kernel"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def _heads(u: jax.Array, positions: jax.Array, lp: Params, cfg: AfmoeConfig,
           kind: str):
    """Normed stream (T, E) -> q (T, H, D), k, v (T, KV, D), q and k
    normed a head and, in a sliding layer, rotated."""
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        q = (u @ _w(lp, "wq", cfg)).reshape(-1, H, D)
        k = (u @ _w(lp, "wk", cfg)).reshape(-1, KV, D)
        v = (u @ _w(lp, "wv", cfg)).reshape(-1, KV, D)
    with jax.named_scope("qk_norm"):
        q = _rms_norm(q, lp["q_norm"]["scale"], cfg.rms_eps)
        k = _rms_norm(k, lp["k_norm"]["scale"], cfg.rms_eps)
    if kind == SLIDING:
        with jax.named_scope("rope"):
            q = _rope_at(q, positions, cfg.rope_theta)
            k = _rope_at(k, positions, cfg.rope_theta)
    return q, k, v


def _gated_out(o: jax.Array, u: jax.Array, lp: Params,
               cfg: AfmoeConfig) -> jax.Array:
    """(T, H, D) mixed heads -> W_o (o * sigmoid(W_g u))."""
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(u @ _w(lp, "wg", cfg))
        o = o.reshape(o.shape[0], -1).astype(cfg.dtype) * gate
    with jax.named_scope("attn_out"):
        return o @ _w(lp, "wo", cfg)


def _swiglu(h: jax.Array, ws: Params, cfg: AfmoeConfig) -> jax.Array:
    gate = jax.nn.silu(h @ ws["w1"].astype(cfg.dtype))
    return (gate * (h @ ws["w3"].astype(cfg.dtype))) \
        @ ws["w2"].astype(cfg.dtype)


def _ffn(h: jax.Array, lp: Params, cfg: AfmoeConfig,
         live: Optional[jax.Array] = None):
    """F on normed rows (N, E) -> (out, the chosen expert ids (N, k) int32
    or None in a dense layer).  ``live`` (N,) bool: a decode step's rows
    that are some sequence's (``ops/moe.choice_of_live_rows``)."""
    from ray_tpu.ops.moe import choice_of_live_rows, dropless_experts, \
        route_sigmoid
    if "mlp" in lp:
        with jax.named_scope("mlp"):
            return _swiglu(h, lp["mlp"], cfg), None
    with jax.named_scope("moe_router"):
        idx, weights = route_sigmoid(
            h, lp["router"]["kernel"], lp["expert_bias"],
            cfg.experts_per_token, cfg.route_scale, eps=1e-20)
    if live is not None:
        idx = choice_of_live_rows(idx, live)
    ex = lp["experts"]
    y, _ = dropless_experts(h, idx, weights, ex["w1"], ex["w3"], ex["w2"],
                            num_experts=cfg.n_experts,
                            first_held=cfg.first_held)
    with jax.named_scope("moe_shared"):
        y = y + _swiglu(h, lp["shared"], cfg)
    return y, idx.astype(jnp.int32)


def _after_attention(x, m, lp, cfg, live=None):
    """The layer from its attention's output ``m`` on: ``x + N2(m)``, then
    ``+ N4(F(N3(.)))`` -> (out, chosen ids or None)."""
    with jax.named_scope("ln_1"):
        x = x + _rms_norm(m.astype(x.dtype), lp["norm2"]["scale"],
                          cfg.rms_eps)
    with jax.named_scope("ln_2"):
        h = _rms_norm(x, lp["norm3"]["scale"], cfg.rms_eps)
    y, ids = _ffn(h, lp, cfg, live)
    with jax.named_scope("ln_2"):
        return x + _rms_norm(y.astype(x.dtype), lp["norm4"]["scale"],
                             cfg.rms_eps), ids


def _stacked_ids(ids: list, rows: int, cfg: AfmoeConfig) -> jax.Array:
    """(routed layers, rows, k) int32; none routed: no layers."""
    if ids:
        return jnp.stack(ids)
    return jnp.zeros((0, rows, cfg.experts_per_token), jnp.int32)


def _kinds(cfg: AfmoeConfig):
    """Each layer's (leaf name, kind, its number among its kind)."""
    seen = {SLIDING: 0, FULL: 0}
    for i, kind in enumerate(cfg.layer_types):
        yield f"l{i:02d}", kind, seen[kind]
        seen[kind] += 1


def _in_kind_order(cfg: AfmoeConfig, per_layer: list) -> jax.Array:
    """Per-layer results in layer order -> stacked with the full layers'
    first, then the sliding layers': the order the cache's two pools are
    written in."""
    order = [i for want in (FULL, SLIDING)
             for i, kind in enumerate(cfg.layer_types) if kind == want]
    return jnp.stack([per_layer[i] for i in order])


# ------------------------------------------------------------------ prefill
def _run(params: Params, tokens: jax.Array, cfg: AfmoeConfig, start,
         staging: Dict[str, jax.Array]):
    """One chunk of one prompt, positions ``start .. start + C - 1``
    (``start`` a multiple of C), through every layer: tokens (C,);
    ``staging`` as :func:`prefill_staging` says, holding every earlier
    chunk.  Returns (the stream (C, E), the staging with this chunk, the
    chunk's own K and V a layer in layer order, the chosen ids a routed
    layer)."""
    C = tokens.shape[0]
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    start = jnp.asarray(start, jnp.int32)
    index = start // C
    positions = start + jnp.arange(C, dtype=jnp.int32)
    n_ring = staging["kw"].shape[1] // C
    held = {FULL: jnp.arange(staging["k"].shape[1] // C, dtype=jnp.int32),
            SLIDING: wattn.ring_chunks(index, n_ring)}
    at = {FULL: start, SLIDING: index % n_ring * C}
    names = {FULL: ("k", "v", "attn_full", None),
             SLIDING: ("kw", "vw", "attn_window", cfg.sliding_window)}
    x = _embed(params, tokens, cfg)
    ks, vs, ids = [], [], []
    for name, kind, number in _kinds(cfg):
        lp = params["layers"][name]
        kn, vn, scope, window = names[kind]
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
        q, k, v = _heads(u, positions, lp, cfg, kind)
        with jax.named_scope("kv_stage"):
            staging = {**staging, **{
                n: lax.dynamic_update_slice(
                    staging[n],
                    a.reshape(1, C, KV * D).astype(jnp.float32),
                    (number, at[kind], 0))
                for n, a in ((kn, k), (vn, v))}}
        with jax.named_scope(scope):
            o = wattn.chunk_attention(
                q.reshape(C, KV, H // KV, D), staging[kn][number],
                staging[vn][number], start, held[kind], window)
        x, chose = _after_attention(
            x, _gated_out(o.reshape(C, H, D), u, lp, cfg), lp, cfg)
        ks.append(k)
        vs.append(v)
        if chose is not None:
            ids.append(chose)
    return x, staging, ks, vs, ids


def forward_prefill_chunk(params: Params, tokens: jax.Array,
                          cfg: AfmoeConfig, start, n_total,
                          staging: Dict[str, jax.Array], state=None,
                          choices: bool = False):
    """One chunk of one prompt: tokens (1, C), its positions ``start ..
    start + C - 1`` of a prompt of ``n_total`` (positions at it and past
    it are padding: they are staged behind every real position's reach and
    change no real position's result).  Returns (logits (1, V) at the
    prompt's last position where this chunk holds it (else at the chunk's
    first), the staging, None: the family carries no recurrent state) and,
    with ``choices``, the experts chosen, (routed layers, C, k) int32."""
    x, staging, _, _, ids = _run(params, tokens[0], cfg, start, staging)
    last = jnp.clip(n_total - 1 - start, 0, tokens.shape[1] - 1)
    x = lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    out = (_logits(params, x, cfg), staging, None)
    return (*out, _stacked_ids(ids, tokens.shape[1], cfg)) if choices \
        else out


def forward_prefill(params: Params, tokens: jax.Array, cfg: AfmoeConfig,
                    last_pos: Optional[jax.Array] = None,
                    choices: bool = False):
    """tokens (B, T) -> (logits, k, v, None), each prompt chunk by chunk
    from an empty staging: k / v (layers, B, T, KV, D) as they are cached
    (normed keys, rotated in the sliding layers), the full layers' first
    and then the sliding layers' (:func:`cache_layers`' order).  With
    ``choices`` a fifth result: the experts chosen, (routed layers, B x T,
    k) int32.

    ``last_pos`` (traced scalar): logits only at that position, (B, V);
    None returns all of them, (B, T, V)."""
    B, T = tokens.shape
    C = cfg.prefill_chunk
    padded = T + -T % C
    empty = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         prefill_staging(cfg, padded))
    out = []
    for b in range(B):
        toks = jnp.pad(tokens[b], (0, padded - T))
        staging, parts = empty, []
        for start in range(0, padded, C):
            x, staging, ks, vs, ids = _run(params, toks[start:start + C],
                                           cfg, start, staging)
            parts.append((x, _in_kind_order(cfg, ks),
                          _in_kind_order(cfg, vs),
                          _stacked_ids(ids, C, cfg)))
        x, ks, vs, ids = (jnp.concatenate(part, axis=axis)[
            (slice(None),) * axis + (slice(0, T),)]
            for part, axis in zip(zip(*parts), (0, 1, 1, 1)))
        x = x if last_pos is None \
            else lax.dynamic_slice_in_dim(x, last_pos, 1, axis=0)[0]
        out.append((_logits(params, x, cfg), ks, vs, ids))
    logits, ks, vs, ids = (jnp.stack(part, axis=axis) for part, axis in
                           zip(zip(*out), (0, 1, 1, 1)))
    result = (logits, ks.astype(cfg.dtype), vs.astype(cfg.dtype), None)
    if choices:
        result += (ids.reshape(ids.shape[0], B * T, -1),)
    return result


def forward(params: Params, tokens: jax.Array,
            cfg: AfmoeConfig) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab) float32."""
    return forward_prefill(params, tokens, cfg)[0]


# ------------------------------------------------------------------- decode
def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array, cfg: AfmoeConfig,
                   window_pool: jax.Array, window_tables: jax.Array,
                   choices: bool = False,
                   live: Optional[jax.Array] = None):
    """One decode step over the engine's two paged pools (read-only here):
    ``kv_pool`` the full layers' (its leading axis those layers) read
    through ``block_tables``, ``window_pool`` the sliding layers' read
    through ``window_tables`` (B, MAXB), whose columns wholly behind a
    row's window name no block any more and are not read.

    Returns (logits (B, V) f32, new_k, new_v (layers, B, KV, D), the full
    layers' first and then the sliding layers') and, with ``choices``, the
    experts chosen, (routed layers, B, k) int32.  ``live`` (B,) bool: the
    rows that are not padding up to the bucket, for the routing."""
    from ray_tpu.ops.paged_attention import paged_attention_decode
    x = _embed(params, tokens, cfg)
    ks, vs, ids = [], [], []
    for name, kind, number in _kinds(cfg):
        lp = params["layers"][name]
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
        q, k, v = _heads(u, positions, lp, cfg, kind)
        if kind == SLIDING:
            with jax.named_scope("attn_window"):
                o = paged_attention_decode(
                    q, window_pool, number, window_tables, ctx_lens, k, v,
                    window=cfg.sliding_window)
        else:
            with jax.named_scope("attn_full"):
                o = paged_attention_decode(q, kv_pool, number, block_tables,
                                           ctx_lens, k, v)
        x, chose = _after_attention(x, _gated_out(o, u, lp, cfg), lp, cfg,
                                    live)
        ks.append(k)
        vs.append(v)
        if chose is not None:
            ids.append(chose)
    out = (_logits(params, x, cfg), _in_kind_order(cfg, ks),
           _in_kind_order(cfg, vs))
    return (*out, _stacked_ids(ids, tokens.shape[0], cfg)) if choices \
        else out
