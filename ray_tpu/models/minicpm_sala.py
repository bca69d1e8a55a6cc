"""MiniCPM-SALA: block-sparse attention that chooses its pages beside
Lightning linear-attention layers, one mixer a layer (openbmb;
``model_type`` ``minicpm_sala``; the sparse mixer is MiniCPM4's InfLLM-v2,
arXiv:2506.07900, the linear one Lightning Attention-2, arXiv:2401.04658).

``x`` is a position's stream of ``E``.  ``h_0 = scale_emb * wte[token]``;
every layer::

    h = h + r * Mixer(RMSNorm(h))
    h = h + r * W_down(silu(W_gate u) * W_up u),   u = RMSNorm(h)

with ``r = scale_depth / sqrt(depth_for_scale)`` (the PUBLISHED depth, also
where fewer layers are held); logits ``= W_head RMSNorm(h) / (E /
dim_model_base)``.  No bias anywhere, the head untied.

    lightning-attn   q, k, v = W_q u, W_k u, W_v u as H heads of D; RMSNorm a
                     head on q and k; RoPE over D;  S_t = lambda_h S_(t-1) +
                     k_t v_t^T,  o_t = S_t^T q_t / sqrt(D),  lambda_h =
                     exp(-s_h),  s_h = 2^(-8 h / H), h = 1..H;
                     o = RMSNorm_head(o) * sigmoid(W_g u);  out = W_o o
    minicpm4         q = W_q u (H heads), k, v = W_k u, W_v u (KV heads);
                     RMSNorm a head on q and k; NO RoPE; attention as
                     ``ops/sparse_attention.py`` says (all positions up to
                     ``dense_len``, the chosen blocks past it);
                     o = o * sigmoid(W_g u);  out = W_o o

The recurrence is ``ops/ssm.py``'s with ``dt = 1``, ``A = -s_h``, ``B = k``,
``C = q``, ``x = v`` and a group a head: ``ssd_scan`` over a run of
positions (from the state the run before left, ``state0=``), ``ssm_step``
for a token.  No second recurrence is written here.

Layers that differ in kind.  ``mixer_types`` names each layer's mixer; a
sparse layer holds K/V (and the selector's half-kernels), a Lightning layer
a state of ``H x D x D`` float32, none both: :func:`cache_layers` counts
them, :func:`recurrent_state` describes a row, :func:`page_selector` says
what the cache keeps a page for the selection.  The layers lie apart, one
tree each (``params["layers"]["l<i>"]``), applied one by one: the published
order of mixers has no period.

A prompt in chunks.  :func:`forward_prefill_chunk` runs ``prefill_chunk``
positions of one prompt: the sparse layers' K/V and half-kernels go into a
*staging* the caller keeps from chunk to chunk (:func:`prefill_staging`),
the Lightning state comes in and goes out, and positions at the prompt's
end and past it leave both as they are.  :func:`forward_prefill` is the
same code over a whole prompt as one run.

Random weights.  Every matrix at ``1 / sqrt(fan_in)``; the embedding at
``1 / scale_emb`` (so ``h_0`` has unit variance); the head at ``(E /
dim_model_base) / sqrt(E)`` so that logits have a standard deviation near
1 after the division; norm scales ones.
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
from ray_tpu.ops import sparse_attention as sparse
from ray_tpu.ops import ssm
from ray_tpu.ops.sparse_attention import SparseSpec

Params = Dict[str, Any]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# mixer_types as published, 32 layers
PUBLISHED_MIXERS = (
    (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 2 + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 3)


@dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    max_positions: int = 524288
    n_embd: int = 4096
    n_layer: int = 32
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    ffn_dim: int = 16384
    lightning_heads: int = 32        # lightning_nh = lightning_nkv
    lightning_head_dim: int = 128
    rope_theta: float = 1e4          # the Lightning layers' only
    rms_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_for_scale: int = 32        # the published num_hidden_layers
    dim_model_base: int = 256
    sparse: SparseSpec = SparseSpec()
    # positions one prefill program runs, and the scan's chunk inside it
    prefill_chunk: int = 2048
    scan_chunk: int = 128
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.mixer_types) != self.n_layer \
                or set(self.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"mixer_types must name {self.n_layer} mixers, each "
                f"{SPARSE!r} or {LIGHTNING!r}; got {self.mixer_types}")
        self.sparse.check()
        if self.prefill_chunk % self.sparse.block:
            raise ValueError("a prefill chunk is whole selection blocks")

    def count(self, kind: str) -> int:
        return sum(1 for t in self.mixer_types if t == kind)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth_for_scale)


def minicpm_sala_9b_l16() -> MiniCPMSalaConfig:
    """MiniCPM-SALA's published widths at layers 9-24 of its 32
    (``mixer_types[9:25]``: 4 sparse and 12 Lightning layers, the published
    1 : 3), in the type it is served in
    (``perfbench/configs/minicpm-sala-9b.json``)."""
    return MiniCPMSalaConfig(n_layer=16, mixer_types=PUBLISHED_MIXERS[9:25],
                             dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def tiny(vocab: int = 128) -> MiniCPMSalaConfig:
    """Six layers at a test's size: 2 sparse beside 4 Lightning, 4 query
    heads a KV head, pages of 8 positions, kernels of 4 at stride 2, 4
    blocks chosen past 48 positions, chunks of 32."""
    return MiniCPMSalaConfig(
        vocab_size=vocab, max_positions=512, n_embd=64, n_layer=6,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE, LIGHTNING,
                     LIGHTNING),
        n_head=8, n_kv_head=2, head_dim=8, ffn_dim=96, lightning_heads=4,
        lightning_head_dim=16, dim_model_base=32,
        sparse=SparseSpec(kernel=4, stride=2, block=8, init_blocks=1,
                          window=8, topk=4, dense_len=48),
        prefill_chunk=32, scan_chunk=8, dtype=jnp.float32)


PRESETS = {"minicpm-sala-9b-l16": minicpm_sala_9b_l16, "tiny": tiny}

# Used as stored (float32): the norms' scales, multiplied in float32 by
# _rms_norm.  Every other leaf is cast to cfg.dtype at its use.
WIDE_PARAMS = ("norm", "mlp_norm", "q_norm", "k_norm", "out_norm", "ln_f")


# ------------------------------------------------------------------- params
def _layer_params(key: jax.Array, cfg: MiniCPMSalaConfig,
                  kind: str) -> Params:
    pd, f32 = cfg.param_dtype, jnp.float32
    E, F = cfg.n_embd, cfg.ffn_dim
    k = iter(jax.random.split(key, 8))

    def matrix(rows: int, cols: int):
        return {"kernel": normal_init(next(k), (rows, cols), pd,
                                      1.0 / math.sqrt(rows))}

    if kind == LIGHTNING:
        H, D = cfg.lightning_heads, cfg.lightning_head_dim
        mixer = {"wq": matrix(E, H * D), "wk": matrix(E, H * D),
                 "wv": matrix(E, H * D),
                 "out_norm": {"scale": jnp.ones((D,), f32)}}
    else:
        H, D = cfg.n_head, cfg.head_dim
        mixer = {"wq": matrix(E, H * D), "wk": matrix(E, cfg.n_kv_head * D),
                 "wv": matrix(E, cfg.n_kv_head * D)}
    return {**mixer,
            "wg": matrix(E, H * D), "wo": matrix(H * D, E),
            "q_norm": {"scale": jnp.ones((D,), f32)},
            "k_norm": {"scale": jnp.ones((D,), f32)},
            "norm": {"scale": jnp.ones((E,), f32)},
            "mlp_norm": {"scale": jnp.ones((E,), f32)},
            "w_gate": matrix(E, F), "w_up": matrix(E, F),
            "w_down": matrix(F, E)}


def init_params(rng: jax.Array, cfg: MiniCPMSalaConfig) -> Params:
    """``layers``: one tree a layer (``l<i>``, two digits), its leaves by
    the layer's mixer; the embedding is ``wte``, the head ``lm_head``."""
    keys = iter(jax.random.split(rng, cfg.n_layer + 2))
    E = cfg.n_embd
    return {
        "wte": normal_init(next(keys), (cfg.vocab_size, E), cfg.param_dtype,
                           1.0 / cfg.scale_emb),
        "layers": {f"l{i:02d}": _layer_params(next(keys), cfg, kind)
                   for i, kind in enumerate(cfg.mixer_types)},
        "ln_f": {"scale": jnp.ones((E,), jnp.float32)},
        "lm_head": {"kernel": normal_init(
            next(keys), (E, cfg.vocab_size), cfg.param_dtype,
            (E / cfg.dim_model_base) / math.sqrt(E))},
    }


def recurrent_state(cfg: MiniCPMSalaConfig
                    ) -> Dict[str, jax.ShapeDtypeStruct]:
    """One sequence's recurrent state in one LIGHTNING layer: ``S`` as
    ``ops/ssm.py`` holds it, (heads, v's features, k's features)."""
    D = cfg.lightning_head_dim
    return {"s": jax.ShapeDtypeStruct((cfg.lightning_heads, D, D),
                                      jnp.float32)}


def cache_layers(cfg: MiniCPMSalaConfig) -> Dict[str, int]:
    """The sparse layers hold K/V, the Lightning layers state, none both;
    each kind is numbered in layer order among its own."""
    return {"kv": cfg.count(SPARSE), "state": cfg.count(LIGHTNING)}


def page_selector(cfg: MiniCPMSalaConfig) -> Dict[str, int]:
    """What the serving cache keeps beside a page's K/V for the layers
    that select: a half-kernel (the sum of the keys) every ``stride``
    positions, and the page it wants: ``block`` positions."""
    return {"stride": cfg.sparse.stride, "block": cfg.sparse.block}


def slopes(cfg: MiniCPMSalaConfig) -> jax.Array:
    """``s_h = 2^(-8 h / H)``, h = 1..H: a head's decay is ``exp(-s_h)``."""
    H = cfg.lightning_heads
    return 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)


def prefill_staging(cfg: MiniCPMSalaConfig,
                    positions: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """What a prompt's chunks keep between them beside the state: the
    sparse layers' K and V, a position a row of ``KV x D`` lanes, and their
    half-kernels; ``positions`` whole chunks."""
    layers, f = cfg.count(SPARSE), cfg.n_kv_head * cfg.head_dim
    kv = jax.ShapeDtypeStruct((layers, positions, f), jnp.float32)
    return {"k": kv, "v": kv, "halves": jax.ShapeDtypeStruct(
        (layers, positions // cfg.sparse.stride, f), jnp.float32)}


# ------------------------------------------------------------------ pieces
# Scopes shared with the other decoders (embed, ln_1, attn_qkv, qk_norm,
# rope, attn_out, ln_2, mlp, ln_f, lm_head) and this family's own:
# sparse_select and sparse_attn in a sparse layer; lightning_mixer around a
# Lightning layer's mixer, inside it lightning_scan (a run of positions) or
# lightning_step (a token) around the recurrence.  Metadata only: PERF.md
# section 3 lists the metric that reads each.
def _w(lp: Params, name: str, cfg: MiniCPMSalaConfig) -> jax.Array:
    return lp[name]["kernel"].astype(cfg.dtype)


def _embed(params: Params, tokens: jax.Array,
           cfg: MiniCPMSalaConfig) -> jax.Array:
    with jax.named_scope("embed"):
        return params["wte"].astype(cfg.dtype)[tokens] * cfg.scale_emb


def _logits(params: Params, x: jax.Array,
            cfg: MiniCPMSalaConfig) -> jax.Array:
    with jax.named_scope("ln_f"):
        x = _rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        out = jnp.dot(x, params["lm_head"]["kernel"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)
        return out / (cfg.n_embd / cfg.dim_model_base)


def _mlp(h: jax.Array, lp: Params, cfg: MiniCPMSalaConfig) -> jax.Array:
    with jax.named_scope("ln_2"):
        u = _rms_norm(h, lp["mlp_norm"]["scale"], cfg.rms_eps)
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(u @ _w(lp, "w_gate", cfg))
        return h + cfg.residual_scale * (
            (gate * (u @ _w(lp, "w_up", cfg))) @ _w(lp, "w_down", cfg))


def _heads(u: jax.Array, lp: Params, cfg: MiniCPMSalaConfig, n_q: int,
           n_kv: int, d: int):
    """Normed stream (T, E) -> q (T, n_q, D), k, v (T, n_kv, D), q and k
    normed a head."""
    with jax.named_scope("attn_qkv"):
        q = (u @ _w(lp, "wq", cfg)).reshape(-1, n_q, d)
        k = (u @ _w(lp, "wk", cfg)).reshape(-1, n_kv, d)
        v = (u @ _w(lp, "wv", cfg)).reshape(-1, n_kv, d)
    with jax.named_scope("qk_norm"):
        q = _rms_norm(q, lp["q_norm"]["scale"], cfg.rms_eps)
        k = _rms_norm(k, lp["k_norm"]["scale"], cfg.rms_eps)
    return q, k, v


def _gated_out(o: jax.Array, u: jax.Array, lp: Params,
               cfg: MiniCPMSalaConfig) -> jax.Array:
    """(T, H, D) mixed heads -> W_o (o * sigmoid(W_g u))."""
    with jax.named_scope("attn_out"):
        gate = jax.nn.sigmoid(u @ _w(lp, "wg", cfg))
        return (o.reshape(o.shape[0], -1).astype(cfg.dtype) * gate) \
            @ _w(lp, "wo", cfg)


def _lightning_heads(u, positions, lp, cfg):
    H, D = cfg.lightning_heads, cfg.lightning_head_dim
    q, k, v = _heads(u, lp, cfg, H, H, D)
    with jax.named_scope("rope"):
        return (_rope_at(q, positions, cfg.rope_theta),
                _rope_at(k, positions, cfg.rope_theta), v)


def _lightning_out(y: jax.Array, u: jax.Array, lp: Params,
                   cfg: MiniCPMSalaConfig) -> jax.Array:
    """The recurrence's readout (T, H, D) float32, scaled, normed a head
    and gated."""
    with jax.named_scope("lightning_norm"):
        y = y * (1.0 / math.sqrt(cfg.lightning_head_dim))
        y = y * lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.rms_eps) \
            * lp["out_norm"]["scale"]
    return _gated_out(y, u, lp, cfg)


# ------------------------------------------------------------------ prefill
def _run(params: Params, tokens: jax.Array, cfg: MiniCPMSalaConfig, start,
         n_total, staging: Dict[str, jax.Array],
         state: Dict[str, jax.Array]):
    """A run of one prompt's positions ``start .. start + T - 1`` through
    every layer: tokens (T,); ``n_total``: the prompt's length (positions
    at it and past it are padding); ``staging`` as :func:`prefill_staging`
    says, holding every earlier position; ``state`` ``{"s": (Lightning
    layers, H, D, D)}``: what the run before left (ignored at ``start`` 0).  Returns
    (the stream (T, E), the staging with this run's positions, the state
    after the last real position)."""
    spec, T = cfg.sparse, tokens.shape[0]
    positions = start + jnp.arange(T, dtype=jnp.int32)
    real = positions < n_total
    state = jnp.where(start == 0, 0.0, state["s"])
    decay = -slopes(cfg)
    x = _embed(params, tokens, cfg)
    kv_i = st_i = 0
    states = []
    for i, kind in enumerate(cfg.mixer_types):
        lp = params["layers"][f"l{i:02d}"]
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
        if kind == LIGHTNING:
            with jax.named_scope("lightning_mixer"):
                q, k, v = _lightning_heads(u, positions, lp, cfg)
                with jax.named_scope("lightning_scan"):
                    dt = jnp.broadcast_to(
                        real[None, :, None].astype(jnp.float32),
                        (1, T, cfg.lightning_heads))
                    y, s = ssm.ssd_scan(v[None], dt, decay, k[None], q[None],
                                        cfg.scan_chunk,
                                        state0=state[st_i][None])
                m = _lightning_out(y[0], u, lp, cfg)
            states.append(s[0])
            st_i += 1
        else:
            H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
            q, k, v = _heads(u, lp, cfg, H, KV, D)
            q = q.reshape(T, KV, H // KV, D)
            with jax.named_scope("kv_stage"):
                flat = {"k": k.reshape(T, KV * D), "v": v.reshape(T, KV * D)}
                flat["halves"] = sparse.halves_of(flat["k"], n_total - start,
                                                  spec.stride)
                at = {"k": start, "v": start, "halves": start // spec.stride}
                staging = {name: lax.dynamic_update_slice(
                    staging[name], flat[name].astype(jnp.float32)[None],
                    (kv_i, at[name], 0)) for name in staging}
            with jax.named_scope("sparse_select"):
                mask = sparse.prefill_mask(
                    q, staging["halves"][kv_i].reshape(-1, KV, D), positions,
                    spec)
            with jax.named_scope("sparse_attn"):
                o = sparse.prefill_attention(
                    q, staging["k"][kv_i], staging["v"][kv_i], mask,
                    positions, start + T, spec.block)
            m = _gated_out(o.reshape(T, H, D), u, lp, cfg)
            kv_i += 1
        x = _mlp(x + cfg.residual_scale * m.astype(x.dtype), lp, cfg)
    return x, staging, {"s": jnp.stack(states)}


def forward_prefill_chunk(params: Params, tokens: jax.Array,
                          cfg: MiniCPMSalaConfig, start, n_total,
                          staging: Dict[str, jax.Array],
                          state: Dict[str, jax.Array]):
    """One chunk of one prompt: tokens (1, C), its positions ``start ..
    start + C - 1`` of a prompt of ``n_total``.  Returns (logits (1, V) at
    the prompt's last position where this chunk holds it (else at the
    chunk's first), the staging, the state ``{"s": (Lightning layers, H, D, D)}``)."""
    x, staging, state = _run(params, tokens[0], cfg, start, n_total, staging,
                             state)
    last = jnp.clip(n_total - 1 - start, 0, tokens.shape[1] - 1)
    x = lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    return _logits(params, x, cfg), staging, state


def forward_prefill(params: Params, tokens: jax.Array,
                    cfg: MiniCPMSalaConfig,
                    last_pos: Optional[jax.Array] = None):
    """tokens (B, T) -> (logits, k, v, state), each prompt one run from an
    empty staging: k / v (sparse layers, B, T, KV, D) as they are cached
    (normed keys, no RoPE), ``state`` ``{"s": (Lightning layers, B, H, D,
    D)}`` at ``last_pos``, or None without it.

    ``last_pos`` (traced scalar): logits only at that position, (B, V);
    None returns all of them, (B, T, V)."""
    B, T = tokens.shape
    KV, D, block = cfg.n_kv_head, cfg.head_dim, cfg.sparse.block
    padded = T + -T % block
    n_total = padded if last_pos is None else last_pos + 1
    empty = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         prefill_staging(cfg, padded))
    state0 = {"s": jnp.zeros(
        (cfg.count(LIGHTNING),) + recurrent_state(cfg)["s"].shape,
        jnp.float32)}
    out = []
    for b in range(B):
        toks = jnp.pad(tokens[b], (0, padded - T))
        x, staged, state = _run(params, toks, cfg, 0, n_total, empty, state0)
        x = x[:T] if last_pos is None \
            else lax.dynamic_slice_in_dim(x, last_pos, 1, axis=0)[0]
        out.append((_logits(params, x, cfg),
                    staged["k"][:, :T].reshape(-1, T, KV, D),
                    staged["v"][:, :T].reshape(-1, T, KV, D), state["s"]))
    logits, ks, vs, state = (jnp.stack(part, axis=axis) for part, axis in
                             zip(zip(*out), (0, 1, 1, 1)))
    return (logits, ks.astype(cfg.dtype), vs.astype(cfg.dtype),
            None if last_pos is None else {"s": state})


def forward(params: Params, tokens: jax.Array,
            cfg: MiniCPMSalaConfig) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab) float32."""
    return forward_prefill(params, tokens, cfg)[0]


# ------------------------------------------------------------------- decode
def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array, cfg: MiniCPMSalaConfig,
                   state: Dict[str, jax.Array], rows: jax.Array,
                   selector: jax.Array):
    """One decode step over the engine's paged K/V pool and selector's
    cache (read-only here, their leading axis the sparse layers) and its
    store of Lightning states (leading axis the Lightning layers).

    ``state``: ``{"s": (state layers, R, H, D, D)}``; ``rows`` (B,) the
    store row of each batch row (one outside the store has none: it reads
    any and writes nowhere); ``selector`` (sparse layers, N, halves a page,
    F).  Returns (logits (B, V) f32, new_k, new_v (sparse layers, B, KV,
    D), the store with the named rows stepped, pages (2,) int32: the pages
    the step's sparse layers read and the pages its rows' contexts hold,
    each summed over rows, sparse layers and KV heads)."""
    from ray_tpu.ops.paged_attention import paged_attention_decode
    spec, B = cfg.sparse, tokens.shape[0]
    store = state["s"]
    read_rows = jnp.minimum(rows, store.shape[1] - 1)
    decay = -slopes(cfg)
    x = _embed(params, tokens, cfg)
    kv_i = st_i = 0
    ks, vs, read = [], [], jnp.int32(0)
    for i, kind in enumerate(cfg.mixer_types):
        lp = params["layers"][f"l{i:02d}"]
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
        if kind == LIGHTNING:
            with jax.named_scope("lightning_mixer"):
                q, k, v = _lightning_heads(u, positions, lp, cfg)
                with jax.named_scope("lightning_step"):
                    y, stepped = ssm.ssm_step(
                        store[st_i][read_rows], v,
                        jnp.ones((B, cfg.lightning_heads), jnp.float32),
                        decay, k, q)
                    store = store.at[st_i, rows].set(stepped, mode="drop")
                m = _lightning_out(y, u, lp, cfg)
            st_i += 1
        else:
            q, k, v = _heads(u, lp, cfg, cfg.n_head, cfg.n_kv_head,
                             cfg.head_dim)
            with jax.named_scope("sparse_select"):
                pages, counts = sparse.decode_pages(
                    q, selector[kv_i], block_tables, ctx_lens, k, spec)
                read = read + counts.sum()
            with jax.named_scope("sparse_attn"):
                o = paged_attention_decode(q, kv_pool, kv_i, block_tables,
                                           ctx_lens, k, v, pages, counts)
            m = _gated_out(o, u, lp, cfg)
            ks.append(k)
            vs.append(v)
            kv_i += 1
        x = _mlp(x + cfg.residual_scale * m.astype(x.dtype), lp, cfg)
    held = (-(-ctx_lens // spec.block)).sum() * (kv_i * cfg.n_kv_head)
    return (_logits(params, x, cfg), jnp.stack(ks), jnp.stack(vs),
            {"s": store}, jnp.stack([read, held]).astype(jnp.int32))
