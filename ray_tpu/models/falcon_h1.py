"""Falcon-H1: a Mamba-2 mixer beside grouped-query attention in every
block (TII, 2025; ``transformers``' ``modeling_falcon_h1.py``).

One block, every layer alike::

    u = RMSNorm(x)
    m = mixer(u)            Mamba-2: in-projection [z | x B C | dt], causal
                            conv + silu over x B C, the SSD recurrence,
                            gated grouped RMSNorm, out-projection
    a = attention(u)        grouped-query, RoPE (rotate-half), no bias
    h = x + ssm_out_multiplier * m + attention_out_multiplier * a
    y = h + SwiGLU(RMSNorm(h))

with muP multipliers on the embedding, the head, the keys, the mixer's
input and the five segments of its projection, both branch outputs and
the MLP's gate and down-projection.  Layout follows llama.py: block
leaves stacked on a leading ``n_layer`` axis, ``lax.scan`` over layers,
and from there ``_rms_norm``, ``_rope`` / ``_rope_at`` and the SwiGLU
form; the mixer's arithmetic is ``ops/ssm.py``.

Serving.  A sequence of this family holds, beside its K/V, a *recurrent
state* per layer: the scan's ``(H, P, N)`` state and the conv's last
``K-1`` inputs, float32.  :func:`recurrent_state` describes one
sequence's, per layer; that export is what tells the serving runner and
its cache manager that the family has one (``serve/llm/model_runner.py``;
a module without it is served as before).  ``forward_prefill`` returns
the state at the prompt's last real position; ``forward_decode`` takes
the cache's store of every sequence's state with the store row of each
batch row, steps the rows that are named and leaves the others as they
are.  The store stays where it lies: a step permutes the token's small
projections into the store's order, updates the store in one pass, and
permutes the results back.

Random weights.  The multipliers assume trained scales; at one common
std the keys would come out at 0.015, the softmax a plain mean, and a
wrong block table would change nothing.  ``init_params`` draws every
matrix at the std that gives its output unit variance *after* its
multiplier, and the recurrence's ``A``, ``dt_bias`` and ``D`` as Mamba-2
initialises them (heads that remember over 10 to 1,000 tokens).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models._common import (  # noqa: F401
    _gqa_expand, _rms_norm, _rope, _rope_at, normal_init, param_count)
from ray_tpu.ops import ssm

Params = Dict[str, Any]


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    max_positions: int = 262144
    n_embd: int = 5120
    n_layer: int = 72
    n_head: int = 20
    n_kv_head: int = 4
    head_dim: int = 128              # not n_embd / n_head: 20 x 128 = 2,560
    ffn_dim: int = 21504
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    # -- the mixer ---------------------------------------------------------
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256             # N: columns of a head's state
    ssm_groups: int = 2              # heads share B and C in groups
    conv_width: int = 4
    ssm_chunk: int = 128
    # -- muP multipliers, as published ------------------------------------
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # the projection's segments [z | x | B | C | dt]
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    dtype: Any = jnp.bfloat16
    # what init_params draws the matrices in; the leaves of WIDE_PARAMS
    # are float32 whatever this says
    param_dtype: Any = jnp.float32
    # ops.attention.causal_attention chooses by it (prefill)
    attn_impl: str = "auto"

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: x and the groups' B and C."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_segments(self) -> Tuple[int, ...]:
        """Widths of the in-projection's segments [z | x | B | C | dt]."""
        gn = self.ssm_groups * self.ssm_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)


def falcon_h1_34b_l6() -> FalconH1Config:
    """Falcon-H1-34B's published sizes at 6 of its 72 layers, in the type
    it is served in (``perfbench/configs/falcon-h1-34b.json``)."""
    return FalconH1Config(n_layer=6, dtype=jnp.bfloat16,
                          param_dtype=jnp.bfloat16)


def tiny(vocab: int = 128, seq: int = 128) -> FalconH1Config:
    """The block at a test's size: every ratio that shapes the code is
    kept unequal (heads x head_dim != n_embd, 2 query heads a KV head,
    2 mixer heads a group, several chunks in a short prompt)."""
    return FalconH1Config(
        vocab_size=vocab, max_positions=seq, n_embd=64, n_layer=2, n_head=4,
        n_kv_head=2, head_dim=32, ffn_dim=128, ssm_heads=4, ssm_head_dim=16,
        ssm_state=16, ssm_groups=2, ssm_chunk=8)


PRESETS = {"falcon-h1-34b-l6": falcon_h1_34b_l6, "tiny": tiny}

# Used as stored (float32): the norms' scales, multiplied in float32 by
# _rms_norm, and the recurrence's per-head A_log, D and dt_bias.  Every
# other leaf is cast to cfg.dtype at its use.
WIDE_PARAMS = ("norm", "mlp_norm", "ssm_norm", "norm_f", "A_log", "D",
               "dt_bias")

# A decode step walks the batch's rows of the scan's states where they lie
# in the store (ops/ssm.ssm_step_rows), not the store's every row.
STATE_BY_ROWS = True


def _over_segments(cfg: FalconH1Config, values) -> jax.Array:
    """One value a segment of the mixer's projection, as a vector over
    its columns."""
    return jnp.concatenate([jnp.full((w,), v, jnp.float32)
                            for w, v in zip(cfg.ssm_proj_segments, values)])


# ------------------------------------------------------------------- params
def init_params(rng: jax.Array, cfg: FalconH1Config) -> Params:
    """Block leaves stacked on a leading n_layer axis, each drawn in one
    call.  Every matrix at the std that gives its output unit variance
    for unit-variance input after its multiplier (the segments of the
    mixer's projection each at their own)."""
    pd, f32 = cfg.param_dtype, jnp.float32
    E, L, F, V = cfg.n_embd, cfg.n_layer, cfg.ffn_dim, cfg.vocab_size
    H, D, KV = cfg.n_head, cfg.head_dim, cfg.n_kv_head
    k = iter(jax.random.split(rng, 16))

    def unit(fan_in: int, multiplier: float = 1.0) -> float:
        return 1.0 / (multiplier * math.sqrt(fan_in))

    def stacked(*shape, scale):
        return normal_init(next(k), (L, *shape), pd, scale)

    seg_std = _over_segments(cfg, [unit(E, m * cfg.ssm_in_multiplier)
                                   for m in cfg.ssm_multipliers])
    w_in = (jax.random.normal(next(k), (L, E, seg_std.shape[0]))
            * seg_std).astype(pd)
    # Mamba-2's: A uniform in [1, 16]; dt log-uniform in [1e-3, 1e-1] and
    # dt_bias its inverse softplus; D ones
    a = jax.random.uniform(next(k), (L, cfg.ssm_heads), f32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(k), (L, cfg.ssm_heads), f32,
                                    math.log(1e-3), math.log(1e-1)))
    blocks = {
        "norm": {"scale": jnp.ones((L, E), f32)},
        "ssm_in": {"kernel": w_in},
        "conv": {"kernel": stacked(cfg.conv_width, cfg.conv_dim,
                                   scale=unit(cfg.conv_width)),
                 "bias": stacked(cfg.conv_dim, scale=0.1)},
        "A_log": jnp.log(a),
        "D": jnp.ones((L, cfg.ssm_heads), f32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "ssm_norm": {"scale": jnp.ones((L, cfg.d_ssm), f32)},
        "ssm_out": {"kernel": stacked(
            cfg.d_ssm, E, scale=unit(cfg.d_ssm, cfg.ssm_out_multiplier))},
        "wq": {"kernel": stacked(
            E, H * D, scale=unit(E, cfg.attention_in_multiplier))},
        "wk": {"kernel": stacked(
            E, KV * D, scale=unit(E, cfg.attention_in_multiplier
                                  * cfg.key_multiplier))},
        "wv": {"kernel": stacked(
            E, KV * D, scale=unit(E, cfg.attention_in_multiplier))},
        "wo": {"kernel": stacked(
            H * D, E, scale=unit(H * D, cfg.attention_out_multiplier))},
        "mlp_norm": {"scale": jnp.ones((L, E), f32)},
        "w_gate": {"kernel": stacked(
            E, F, scale=unit(E, cfg.mlp_multipliers[0]))},
        "w_up": {"kernel": stacked(E, F, scale=unit(E))},
        "w_down": {"kernel": stacked(
            F, E, scale=unit(F, cfg.mlp_multipliers[1]))},
    }
    return {
        "wte": normal_init(next(k), (V, E), pd,
                           1.0 / cfg.embedding_multiplier),
        "blocks": blocks,
        "norm_f": {"scale": jnp.ones((E,), f32)},
        "lm_head": {"kernel": normal_init(
            next(k), (E, V), pd, unit(E, cfg.lm_head_multiplier))},
    }


def recurrent_state(cfg: FalconH1Config) -> Dict[str, jax.ShapeDtypeStruct]:
    """One sequence's recurrent state in one layer: what the serving
    cache keeps a row of per sequence and layer (a store leaf is
    ``(n_layer, rows, *shape)``), what ``forward_prefill`` returns per
    prompt and ``forward_decode`` steps."""
    return {
        "ssm": jax.ShapeDtypeStruct(
            (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (cfg.conv_width - 1, cfg.conv_dim), jnp.float32),
    }


# ------------------------------------------------------------------ pieces
def _scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """``multiplier * x`` in x's type, multiplied in float32: a bf16
    multiply would round the multiplier itself to 8 bits."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


# The layer kinds shared with the other decoders run under GPT-2's
# ``jax.named_scope`` names (embed, ln_1, attn_qkv, attn_out, ln_2, mlp,
# ln_f, lm_head; models/gpt2.py), the mixer's under ssm_*.  Metadata only:
# PERF.md section 3 lists the metric that reads each.
def _embed(params: Params, tokens: jax.Array, cfg: FalconH1Config):
    with jax.named_scope("embed"):
        return _scaled(params["wte"].astype(cfg.dtype)[tokens],
                       cfg.embedding_multiplier)


def _logits(params: Params, x: jax.Array, cfg: FalconH1Config) -> jax.Array:
    with jax.named_scope("ln_f"):
        x = _rms_norm(x, params["norm_f"]["scale"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.dot(x, params["lm_head"]["kernel"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        return logits * cfg.lm_head_multiplier


def _qkv(u: jax.Array, lp: Params, cfg: FalconH1Config):
    """Normed hidden states (..., E) -> q (..., H, D), k, v (..., KV, D),
    before RoPE, the keys scaled."""
    H, D, KV = cfg.n_head, cfg.head_dim, cfg.n_kv_head
    with jax.named_scope("attn_qkv"):
        a_in = _scaled(u, cfg.attention_in_multiplier)
        q = a_in @ lp["wq"]["kernel"].astype(cfg.dtype)
        k = _scaled(a_in @ lp["wk"]["kernel"].astype(cfg.dtype),
                    cfg.key_multiplier)
        v = a_in @ lp["wv"]["kernel"].astype(cfg.dtype)
    lead = u.shape[:-1]
    return (q.reshape(*lead, H, D), k.reshape(*lead, KV, D),
            v.reshape(*lead, KV, D))


def _mlp(h: jax.Array, lp: Params, cfg: FalconH1Config) -> jax.Array:
    gate_m, down_m = cfg.mlp_multipliers
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(_scaled(
            h @ lp["w_gate"]["kernel"].astype(cfg.dtype), gate_m))
        up = h @ lp["w_up"]["kernel"].astype(cfg.dtype)
        return _scaled(
            (gate * up) @ lp["w_down"]["kernel"].astype(cfg.dtype), down_m)


def _residual(x, m, a, cfg: FalconH1Config):
    """x + ssm_out_multiplier * m + attention_out_multiplier * a."""
    f32 = jnp.float32
    with jax.named_scope("residual"):
        return (x.astype(f32) + m.astype(f32) * cfg.ssm_out_multiplier
                + a.astype(f32) * cfg.attention_out_multiplier
                ).astype(x.dtype)


def _ssm_project(u: jax.Array, lp: Params, cfg: FalconH1Config) -> jax.Array:
    """Normed hidden states (..., E) -> the mixer's projection (...,
    [z | x | B | C | dt]) in float32, each segment at its multiplier."""
    with jax.named_scope("ssm_in"):
        p = _scaled(u, cfg.ssm_in_multiplier) \
            @ lp["ssm_in"]["kernel"].astype(cfg.dtype)
        return p.astype(jnp.float32) * _over_segments(cfg,
                                                      cfg.ssm_multipliers)


def _split_proj(p: jax.Array, cfg: FalconH1Config):
    """The projection -> (z (..., d_ssm), xBC (..., conv_dim), dt (..., H))."""
    return (p[..., :cfg.d_ssm], p[..., cfg.d_ssm:cfg.d_ssm + cfg.conv_dim],
            p[..., cfg.d_ssm + cfg.conv_dim:])


def _split_conv(xbc: jax.Array, cfg: FalconH1Config):
    """The conv's activated output -> x (..., H, P), B, C (..., G, N)."""
    lead = xbc.shape[:-1]
    gn = cfg.ssm_groups * cfg.ssm_state
    x = xbc[..., :cfg.d_ssm].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    b = xbc[..., cfg.d_ssm:cfg.d_ssm + gn]
    c = xbc[..., cfg.d_ssm + gn:]
    shape = (*lead, cfg.ssm_groups, cfg.ssm_state)
    return x, b.reshape(shape), c.reshape(shape)


def _gated_norm(y: jax.Array, z: jax.Array, lp: Params,
                cfg: FalconH1Config) -> jax.Array:
    """RMSNorm over each group of ``y * silu(z)`` (the gate first:
    ``mamba_norm_before_gate`` false), float32 in, cfg.dtype out."""
    with jax.named_scope("ssm_norm"):
        return ssm.gate_then_group_norm(y, z, lp["ssm_norm"]["scale"],
                                        cfg.ssm_groups, cfg.rms_eps,
                                        cfg.dtype)


def _ssm_out(g: jax.Array, lp: Params, cfg: FalconH1Config) -> jax.Array:
    with jax.named_scope("ssm_out"):
        return g @ lp["ssm_out"]["kernel"].astype(cfg.dtype)


def _dt(dt_raw: jax.Array, lp: Params) -> jax.Array:
    return jax.nn.softplus(dt_raw + lp["dt_bias"])


def _mixer(u: jax.Array, lp: Params, cfg: FalconH1Config,
           last_pos: Optional[jax.Array]):
    """The mixer over a sequence (B, T, E) from a zero state -> (m (B, T,
    E), the state at ``last_pos`` as in :func:`recurrent_state` with a
    leading batch axis, or None without ``last_pos``).  Positions past
    ``last_pos`` (a prompt's padding) leave the state as it is."""
    z, xbc, dt_raw = _split_proj(_ssm_project(u, lp, cfg), cfg)
    with jax.named_scope("ssm_conv"):
        xbc, tail = ssm.causal_conv(xbc, lp["conv"]["kernel"],
                                    lp["conv"]["bias"], last_pos)
        x, b, c = _split_conv(jax.nn.silu(xbc), cfg)
    with jax.named_scope("ssm_scan"):
        dt = _dt(dt_raw, lp)
        if last_pos is not None:
            real = jnp.arange(u.shape[1]) <= last_pos
            dt = jnp.where(real[None, :, None], dt, 0.0)
        y, state = ssm.ssd_scan(x, dt, -jnp.exp(lp["A_log"]), b, c,
                                cfg.ssm_chunk)
        y = y + lp["D"][:, None] * x
    m = _ssm_out(_gated_norm(y, z, lp, cfg), lp, cfg)
    if last_pos is None:
        return m, None
    return m, {"ssm": state, "conv": tail}


def _store_order(rows: jax.Array, n_rows: int):
    """How a decode batch maps onto a store of ``n_rows`` rows.  ``rows``
    (B,): the store row of each batch row; one outside the store has no
    state (a row padded up to the bucket): it reads any and writes none.
    Returns (live (R,): a batch row steps this store row; source (R,):
    which; back (B,): the store row a batch row reads its result from)."""
    hit = rows[None, :] == jnp.arange(n_rows)[:, None]            # (R, B)
    return hit.any(1), jnp.argmax(hit, 1), jnp.minimum(rows, n_rows - 1)


def _mixer_step(u: jax.Array, lp: Params, cfg: FalconH1Config,
                state: Dict[str, jax.Array], layer, rows: jax.Array, order):
    """The mixer for one token of each batch row (B, E) against layer
    ``layer`` of the store of states (leaves (L, R, ...)) -> (m (B, E), the
    store with that layer's named rows stepped).

    The scan's states, the large part, are stepped where they lie, row by
    batch row (``ops/ssm.ssm_step_rows``: one pass over the batch's rows).
    The conv's tails (60 KB a row) are updated in the store's own order
    (:func:`_store_order`): the conv's input is permuted into it and its
    output back, which costs XLA 0.05 ms a step where a gather and a
    scatter of the rows by index would be 64 dependent row copies."""
    live, source, back = order
    z, xbc, dt_raw = _split_proj(_ssm_project(u, lp, cfg), cfg)
    # the layer's tails out of the store and back into it count with the
    # step, as the store's other passes do
    with jax.named_scope("ssm_step"):
        tails = lax.dynamic_index_in_dim(state["conv"], layer, 0,
                                         keepdims=False)
    with jax.named_scope("ssm_conv"):
        xbc, tail = ssm.conv_step(tails, xbc[source], lp["conv"]["kernel"],
                                  lp["conv"]["bias"])
        x, b, c = _split_conv(jax.nn.silu(xbc)[back], cfg)
    with jax.named_scope("ssm_step"):
        y, scan = ssm.ssm_step_rows(state["ssm"], layer, rows, x,
                                    _dt(dt_raw, lp), -jnp.exp(lp["A_log"]),
                                    b, c)
        y = y + lp["D"][:, None] * x
        tails = jnp.where(live[:, None, None], tail, tails)
        state = {"ssm": scan, "conv": lax.dynamic_update_index_in_dim(
            state["conv"], tails, layer, 0)}
    return _ssm_out(_gated_norm(y, z, lp, cfg), lp, cfg), state


# ------------------------------------------------------------------ forward
def forward_prefill(params: Params, tokens: jax.Array, cfg: FalconH1Config,
                    last_pos: Optional[jax.Array] = None):
    """tokens (B, T) -> (logits, k, v, state): k / v (L, B, T, KV, D) as
    llama.forward_prefill caches them (keys post-RoPE, values before the
    groups are expanded), ``state`` the recurrent state at ``last_pos``,
    leaves (L, B, ...), or None without it.

    ``last_pos`` (traced scalar): logits only at that position, (B, V);
    None returns all of them, (B, T, V)."""
    from ray_tpu.ops.attention import causal_attention
    B, T = tokens.shape
    H = cfg.n_head

    def body(x, lp):
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
        m, state = _mixer(u, lp, cfg, last_pos)
        q, k, v = _qkv(u, lp, cfg)
        with jax.named_scope("rope"):
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        with jax.named_scope("attn"):
            a = causal_attention(q, _gqa_expand(k, H), _gqa_expand(v, H),
                                 impl=cfg.attn_impl)
        with jax.named_scope("attn_out"):
            a = a.reshape(B, T, H * cfg.head_dim) \
                @ lp["wo"]["kernel"].astype(cfg.dtype)
        h = _residual(x, m, a, cfg)
        with jax.named_scope("ln_2"):
            n = _rms_norm(h, lp["mlp_norm"]["scale"], cfg.rms_eps)
        return h + _mlp(n, lp, cfg), (k, v, state)

    x, (ks, vs, state) = lax.scan(body, _embed(params, tokens, cfg),
                                  params["blocks"])
    if last_pos is not None:
        x = lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)[:, 0]
    return _logits(params, x, cfg), ks, vs, state


def forward(params: Params, tokens: jax.Array,
            cfg: FalconH1Config) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab) float32."""
    return forward_prefill(params, tokens, cfg)[0]


def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array, cfg: FalconH1Config,
                   state: Dict[str, jax.Array], rows: jax.Array):
    """One decode step over the engine's paged K/V pool (read-only here,
    as in llama.forward_decode) and its store of recurrent state.

    ``state``: the store, leaves ``(L, R, *recurrent_state(cfg)[name]
    .shape)``; ``rows`` (B,) the store row of each batch row (one outside
    the store: none).  Returns (logits (B, V) f32, new_k, new_v (L, B,
    KV, D), the store with the named rows stepped)."""
    from ray_tpu.ops.paged_attention import paged_attention_decode
    B = tokens.shape[0]
    order = _store_order(rows, state["ssm"].shape[1])

    def body(carry, xs):
        x, state = carry
        lp, layer = xs
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
        m, state = _mixer_step(u, lp, cfg, state, layer, rows, order)
        q, k, v = _qkv(u, lp, cfg)
        with jax.named_scope("rope"):
            q = _rope_at(q, positions, cfg.rope_theta)
            k = _rope_at(k, positions, cfg.rope_theta)
        a = paged_attention_decode(q, kv_pool, layer, block_tables,
                                   ctx_lens, k, v)
        with jax.named_scope("attn_out"):
            a = a.reshape(B, cfg.n_head * cfg.head_dim) \
                @ lp["wo"]["kernel"].astype(cfg.dtype)
        h = _residual(x, m, a, cfg)
        with jax.named_scope("ln_2"):
            n = _rms_norm(h, lp["mlp_norm"]["scale"], cfg.rms_eps)
        return (h + _mlp(n, lp, cfg), state), (k, v)

    (x, state), (ks, vs) = lax.scan(
        body, (_embed(params, tokens, cfg), state),
        (params["blocks"], jnp.arange(cfg.n_layer)))
    return _logits(params, x, cfg), ks, vs, state
