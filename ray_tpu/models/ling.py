"""Ling 3.0 (inclusionAI; the language model of Ling-3.0-flash-VL): Kimi
Delta Attention layers and multi-head latent attention layers mixed five to
one, a head-wise sigmoid gate on every mixer's output, and group-limited
sigmoid-routed experts beside a shared one behind leading dense layers.

``x`` is a position's stream of ``E``.  ``x_0 = wte[token]``; every layer::

    x = x + Mixer(N1(x))
    x = x + F(N2(x))

pre-norm RMSNorms; after the last a final RMSNorm and the untied head.  No
bias anywhere.  Layer ``i`` of the published stack is MLA where ``(i + 1) %
layer_group_size == 0`` and KDA otherwise (``mixer_types`` names the layers
held); the first ``n_dense_layer`` have the dense F.

    KDA(u)    [q | k | v] = silu(conv4(W_qkv u))   depthwise causal, no
              bias; H heads of D each.  q = l2norm(q) / sqrt(D), k =
              l2norm(k) a head; beta = sigmoid(W_b u) a head; the decay a
              CHANNEL of each head's key, float32:
              g = lower_bound * sigmoid(exp(A_log_h) (W_f u + dt_bias)),
              so lower_bound < g < 0.  Per head, state S (D x D, float32,
              zero at the start):
                  S <- diag(exp(g_t)) S;  d_t = beta_t (v_t - S^T k_t)
                  S <- S + k_t d_t^T;     o_t = S^T q_t
              out = W_o (sigmoid(W_g u)_h * rmsnorm_D(o_t)); the norm over
              a head's D values with one learned D-scale, the gate one
              number a head.  No positions enter (``ops/delta_rule.py``:
              ``kda_step`` a token, ``kda_chunks`` a run).
    MLA(u)    [c | k_r] = W_kva u (kv_lora_rank | rope); c = rmsnorm(c);
              q = W_q u as H x (nope | rope) (no query latent); RoPE on
              interleaved pairs (2i, 2i + 1) of q_r and of k_r, which all
              heads share; [k_n | v]_h = W_kvb,h c; scores (q_n . k_n + q_r
              . k_r) / sqrt(nope + rope), causal softmax in float32; o_h =
              sum p v; out = W_o (sigmoid(W_g u)_h * o_h).
              CACHED: the row [c | k_r], ``latent_row`` features.
              Absorbed (a decode step): q_c = q_n W_uk,h; score (q_c . c +
              q_r . k_r) / sqrt(nope + rope); o_c = sum p c; o_h = o_c
              W_uv,h, with W_uk | W_uv the halves of W_kvb,h.  A prefill
              chunk up-projects the prompt's rows so far and attends with
              the (nope + rope) / v heads.
    F dense   W_2 (silu(W_1 h) * W_3 h)
    F routed  s = sigmoid(W_r h) in float32 over all ``n_experts``; sel = s
              + expert_bias; the experts in ``n_group`` groups by id, a
              group's score the sum of its two largest sel, the
              ``topk_group`` best groups stay and the k largest sel among
              their experts are picked; weights route_scale s_e / (sum of
              the picked s + 1e-20); out = SwiGLU_shared(h) + the weighted
              sum of the picked experts' SwiGLU(h)

A share of the experts.  ``held_experts`` of the ``n_experts`` the router
scores lie here, from ``first_held`` on: one chip's share of a layer that
several chips hold by its experts, one routing group a chip
(``ops/moe.dropless_experts``).  A token's weights are normalised over all
it picked; what the absent experts would add is another chip's part and is
left out; the shared expert is whole here.

Serving.  A KDA layer holds a ROW of state a sequence: ``S`` and the
conv's tail (:func:`recurrent_state`); an MLA layer a latent PAGE, one row
a position.  :func:`cache_layers` counts the kinds (``"latent"``, ``"state"``,
no ``"kv"``); the cache keeps the store and a pool of one plane under the
one block table (``serve/llm/kv_cache.py``, a latent page), each kind's
layers numbered in layer order among their own.  ``forward_decode`` steps
the rows the engine names and reads the latent pool through the absorbed
kernel (``ops/paged_attention.latent_attention_decode``); the new rows it
returns as its ``k`` are written by the runner.

A prompt in chunks.  :func:`forward_prefill_chunk` runs ``prefill_chunk``
positions of one prompt over a *staging* of the prompt's latent rows so far
(:func:`prefill_staging`) and over the state the chunk before left;
positions past the prompt's end move neither.  :func:`forward_prefill` is
the same code over a whole prompt, chunk by chunk.

Random weights.  Every matrix at ``1 / sqrt(fan_in)``; the embedding at 1,
so the stream's RMS is near 1; the conv's taps at ``1 / sqrt(K)``; norm
scales ones; ``A_log`` near 0 and ``dt_bias`` at -3 +- 2, so the channels'
decays reach from a step's -0.005 to its -4: long and short memories;
``expert_bias`` at 0.01.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models._common import _rms_norm, normal_init
from ray_tpu.ops import delta_rule, ssm
from ray_tpu.ops import window_attention as wattn

Params = Dict[str, Any]

KDA, MLA = "kda", "mla"


def published_mixers(n_layer: int = 42, group: int = 6) -> Tuple[str, ...]:
    """The published stack's mixers: the last layer of every group of
    ``group`` is MLA."""
    return tuple(MLA if (i + 1) % group == 0 else KDA for i in range(n_layer))


@dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157184
    max_positions: int = 131072
    n_embd: int = 2560
    n_layer: int = 42
    n_dense_layer: int = 2           # first_k_dense_replace: they lead
    mixer_types: Tuple[str, ...] = published_mixers()
    n_head: int = 32                 # both mixers'
    head_dim: int = 128              # a KDA head's keys and values
    conv_kernel: int = 4             # short_conv_kernel_size
    kda_lower_bound: float = -5.0    # kda_safe_gate: a step's decay above it
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 6144              # the dense layers' SwiGLU
    expert_dim: int = 768            # moe_intermediate_size, the shared too
    n_experts: int = 512             # what the router scores
    experts_per_token: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    route_scale: float = 2.5         # routed_scaling_factor
    # the experts held here, ``first_held .. first_held + held_experts - 1``
    # (0: all of them)
    held_experts: int = 0
    first_held: int = 0
    # expert_swiglu_limit_list / share_expert_swiglu_limit_list of the
    # layers held: a clamp inside the experts' SwiGLU, 0 for none.  Only 0
    # is written (the published clamps start at layers 35 and 34)
    swiglu_limits: Tuple[float, ...] = ()
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    # positions one prefill program runs, and one chunk of the rule
    prefill_chunk: int = 2048
    kda_chunk: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.mixer_types) != self.n_layer \
                or set(self.mixer_types) - {KDA, MLA}:
            raise ValueError(
                f"mixer_types must name {self.n_layer} layers, each "
                f"{KDA!r} or {MLA!r}; got {self.mixer_types}")
        if not 0 <= self.n_dense_layer <= self.n_layer:
            raise ValueError(f"{self.n_dense_layer} dense layers of "
                             f"{self.n_layer}")
        if not 0 <= self.first_held <= self.n_experts - self.held:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + self.held - 1}"
                f" are not among the router's {self.n_experts}")
        if self.n_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError(f"{self.n_experts} experts in {self.n_group} "
                             f"groups, {self.topk_group} of them chosen")
        if any(self.swiglu_limits):
            raise NotImplementedError(
                f"a SwiGLU limit ({self.swiglu_limits}): the published "
                "clamps lie in layers 34-41, and no layer held has one")
        if self.prefill_chunk % self.kda_chunk:
            raise ValueError(f"chunks of {self.prefill_chunk} positions are "
                             f"no whole chunks of the rule's {self.kda_chunk}")

    @property
    def held(self) -> int:
        return self.held_experts or self.n_experts

    @property
    def latent_row(self) -> int:
        """The features of the row an MLA layer caches a position."""
        return self.kv_lora_rank + self.qk_rope_dim

    def count(self, kind: str) -> int:
        return sum(1 for t in self.mixer_types if t == kind)


def ling_flash_l7() -> LingConfig:
    """Ling-3.0-flash's published widths at 7 of its 42 layers, published
    layers 1-7 in their order: one of the two leading dense layers (KDA)
    and one whole period of six routed layers (KDA, KDA, KDA, MLA, KDA,
    KDA), one routing group of each layer's experts (64 of 512: one of 8
    chips' share), 19,648 of the 157,184 vocabulary rows, in the type it is
    served in (``perfbench/configs/ling-3.0-flash-vl.json``)."""
    return LingConfig(
        vocab_size=19648, n_layer=7, n_dense_layer=1,
        mixer_types=published_mixers()[1:8], held_experts=64,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def tiny(vocab: int = 128, first_held: int = 0) -> LingConfig:
    """Four layers at a test's size: a dense KDA layer, then KDA, MLA, KDA,
    routed; 4 heads of 8, a latent of 16 + 4 rotary, chunks of 32 (the
    rule's 16), 8 experts in 2 groups of which one is chosen, 4 held, 2 a
    token."""
    return LingConfig(
        vocab_size=vocab, max_positions=512, n_embd=64, n_layer=4,
        n_dense_layer=1, mixer_types=(KDA, KDA, MLA, KDA), n_head=4,
        head_dim=8, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
        v_head_dim=8, ffn_dim=96, expert_dim=48, n_experts=8,
        experts_per_token=2, n_group=2, topk_group=1, held_experts=4,
        first_held=first_held, prefill_chunk=32, kda_chunk=16,
        dtype=jnp.float32)


PRESETS = {"ling-flash-l7": ling_flash_l7, "tiny": tiny}

# Used as stored (float32): the norms' scales, multiplied in float32 by
# _rms_norm, the router's selection bias, added to float32 scores, and the
# decay's two parameters, which enter a float32 gate.  Every other leaf is
# cast to cfg.dtype at its use.
WIDE_PARAMS = ("norm1", "norm2", "kv_norm", "o_norm", "ln_f", "expert_bias",
               "a_log", "dt_bias")


# ------------------------------------------------------------------- params
def _layer_params(key: jax.Array, cfg: LingConfig, kind: str,
                  routed: bool) -> Params:
    pd, f32 = cfg.param_dtype, jnp.float32
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    k = iter(jax.random.split(key, 20))

    def matrix(*shape, fan_in: int):
        return normal_init(next(k), shape, pd, 1.0 / math.sqrt(fan_in))

    def swiglu(width: int, *lead: int):
        return {"w1": matrix(*lead, E, width, fan_in=E),
                "w3": matrix(*lead, E, width, fan_in=E),
                "w2": matrix(*lead, width, E, fan_in=width)}

    lp = {f"norm{i}": {"scale": jnp.ones((E,), f32)} for i in (1, 2)}
    lp["wg"] = {"kernel": matrix(E, H, fan_in=E)}
    if kind == KDA:
        lp.update(
            wqkv={"kernel": matrix(E, 3 * H * D, fan_in=E)},
            conv={"kernel": matrix(cfg.conv_kernel, 3 * H * D,
                                   fan_in=cfg.conv_kernel)},
            wf={"kernel": matrix(E, H * D, fan_in=E)},
            wb={"kernel": matrix(E, H, fan_in=E)},
            a_log=jax.random.normal(next(k), (H,), f32) * 0.3,
            dt_bias=jax.random.normal(next(k), (H * D,), f32) * 2.0 - 3.0,
            o_norm={"scale": jnp.ones((D,), f32)},
            wo={"kernel": matrix(H * D, E, fan_in=H * D)})
    else:
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        lp.update(
            wq={"kernel": matrix(E, H * qk, fan_in=E)},
            wkva={"kernel": matrix(E, cfg.latent_row, fan_in=E)},
            kv_norm={"scale": jnp.ones((cfg.kv_lora_rank,), f32)},
            wkvb={"kernel": matrix(
                cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim),
                fan_in=cfg.kv_lora_rank)},
            wo={"kernel": matrix(H * cfg.v_head_dim, E,
                                 fan_in=H * cfg.v_head_dim)})
    if not routed:
        lp["mlp"] = swiglu(cfg.ffn_dim)
        return lp
    lp["router"] = {"kernel": matrix(E, cfg.n_experts, fan_in=E)}
    lp["expert_bias"] = jax.random.normal(next(k), (cfg.n_experts,),
                                          f32) * 0.01
    lp["shared"] = swiglu(cfg.expert_dim * cfg.n_shared_experts)
    lp["experts"] = swiglu(cfg.expert_dim, cfg.held)
    return lp


def init_params(rng: jax.Array, cfg: LingConfig) -> Params:
    """``layers``: one tree a layer (``l<i>``, two digits), its leaves by
    the layer's mixer and F; the embedding is ``wte``, the head
    ``lm_head``.  A routed layer's ``experts`` are the ``held`` ones,
    ``first_held`` on; its router scores all of them."""
    keys = iter(jax.random.split(rng, cfg.n_layer + 2))
    E = cfg.n_embd
    return {
        "wte": normal_init(next(keys), (cfg.vocab_size, E), cfg.param_dtype,
                           1.0),
        "layers": {f"l{i:02d}": _layer_params(next(keys), cfg, kind,
                                              i >= cfg.n_dense_layer)
                   for i, kind in enumerate(cfg.mixer_types)},
        "ln_f": {"scale": jnp.ones((E,), jnp.float32)},
        "lm_head": {"kernel": normal_init(
            next(keys), (E, cfg.vocab_size), cfg.param_dtype,
            1.0 / math.sqrt(E))},
    }


def recurrent_state(cfg: LingConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """One sequence's recurrent state in one KDA layer: the rule's ``s``
    (heads, keys, values) and the conv's tail, the last ``conv_kernel - 1``
    inputs of q, k and v side by side, one behind another and laid 128
    lanes wide, ``((conv_kernel - 1) 3 H D / 128, 128)``: whole tiles,
    which ``ops/ssm.conv_step_rows`` steps where they lie (the channels
    wide where they are no whole lanes: a test's size); float32."""
    H, D = cfg.n_head, cfg.head_dim
    c = 3 * H * D
    lanes = 128 if c % 128 == 0 else c
    return {"s": jax.ShapeDtypeStruct((H, D, D), jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                ((cfg.conv_kernel - 1) * c // lanes, lanes), jnp.float32)}


def cache_layers(cfg: LingConfig) -> Dict[str, int]:
    """The layers by what they hold: ``latent``: one row ``[c | k_r]`` of
    every position (the MLA layers), ``state``: a row of state a sequence
    (the KDA layers); none holds K/V.  Each kind is numbered in layer order
    among its own."""
    return {"kv": 0, "latent": cfg.count(MLA), "state": cfg.count(KDA)}


def routed_layers(cfg: LingConfig) -> Optional[Dict[str, Any]]:
    """What the step programs hand over beside the logits: the expert ids
    each routed layer chose (of all ``n_experts``, held or not), int32
    (layers, rows, k), in layer order; ``held`` (first, count) where the
    layer's experts here are a share of them."""
    if cfg.n_dense_layer == cfg.n_layer:
        return None
    out = {"layers": cfg.n_layer - cfg.n_dense_layer,
           "k": cfg.experts_per_token}
    if cfg.held < cfg.n_experts:
        out["held"] = (cfg.first_held, cfg.held)
    return out


def prefill_staging(cfg: LingConfig,
                    positions: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """What a prompt's chunks keep between them beside the state: the MLA
    layers' latent rows of ``positions`` positions (whole chunks)."""
    return {"latent": jax.ShapeDtypeStruct(
        (cfg.count(MLA), positions, cfg.latent_row), jnp.float32)}


# ------------------------------------------------------------------ pieces
# Scopes shared with the other decoders (embed, ln_1, attn_qkv, rope,
# attn_gate, attn_out, ln_2, mlp, ln_f, lm_head; the moe_* of ops/moe.py,
# moe_shared, kv_stage) and this family's own: kda_conv round the short
# conv (a decode step's tails stepped where they lie: ops/ssm.
# conv_step_rows), kda_step
# round a decode step's rule, the rows of state stepped where they lie
# (ops/delta_rule.kda_step_rows), kda_chunk round a prefill chunk's rule,
# kda_gate round
# the decay and beta; mla_latent_attn round the paged absorbed kernel,
# mla_absorb round the two products that fold W_kvb into the query and out
# of the result, mla_prefill_attn round a chunk's up-projection and
# attention.  Metadata only: PERF.md section 3 lists the metric that reads
# each.
def _w(lp: Params, name: str, cfg: LingConfig) -> jax.Array:
    return lp[name]["kernel"].astype(cfg.dtype)


def _embed(params: Params, tokens: jax.Array, cfg: LingConfig) -> jax.Array:
    with jax.named_scope("embed"):
        return params["wte"].astype(cfg.dtype)[tokens]


def _logits(params: Params, x: jax.Array, cfg: LingConfig) -> jax.Array:
    with jax.named_scope("ln_f"):
        x = _rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        return jnp.dot(x, params["lm_head"]["kernel"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def rope_pairs(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on the interleaved pairs (2i, 2i + 1) of the last
    axis (``models/deepseek_v3.py``'s pairing): x (T, ..., D), positions
    (T,); position t turns pair i by ``t theta^(-2i / D)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs
    angles = angles.reshape(angles.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def _head_gate(u: jax.Array, lp: Params, cfg: LingConfig) -> jax.Array:
    """(T, H, 1): the head-wise sigmoid gate of a mixer's output."""
    with jax.named_scope("attn_gate"):
        return jax.nn.sigmoid(u @ _w(lp, "wg", cfg))[..., None]


def _kda_heads(y: jax.Array, u: jax.Array, lp: Params, cfg: LingConfig,
               real: Optional[jax.Array] = None):
    """The conv's output y (T, 3 H D) float32 and the normed stream u ->
    q, k, v (T, H, D) in cfg.dtype (q l2-normed and scaled, k l2-normed),
    g (T, H, D) and beta (T, H) float32; ``real`` (T,) bool: the positions
    that are the prompt's (the others get g = 0 and beta = 0, which leave
    the state as it is)."""
    H, D = cfg.n_head, cfg.head_dim
    q, k, v = jnp.split(jax.nn.silu(y).reshape(-1, 3 * H, D), 3, axis=1)
    q = (delta_rule.l2norm(q) * D ** -0.5).astype(cfg.dtype)
    k = delta_rule.l2norm(k).astype(cfg.dtype)
    with jax.named_scope("kda_gate"):
        f = jnp.dot(u, _w(lp, "wf", cfg), preferred_element_type=jnp.float32)
        rate = jnp.exp(lp["a_log"].astype(jnp.float32))[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            rate * (f + lp["dt_bias"]).reshape(-1, H, D))
        beta = jax.nn.sigmoid(jnp.dot(u, _w(lp, "wb", cfg),
                                      preferred_element_type=jnp.float32))
        if real is not None:
            g = jnp.where(real[:, None, None], g, 0.0)
            beta = jnp.where(real[:, None], beta, 0.0)
    return q, k, v.astype(cfg.dtype), g, beta


def _gated_out(o: jax.Array, u: jax.Array, lp: Params,
               cfg: LingConfig) -> jax.Array:
    """(T, H, .) mixed heads -> W_o (gate_h * o): both mixers' way out."""
    o = (o.astype(cfg.dtype) * _head_gate(u, lp, cfg)).reshape(
        o.shape[0], -1)
    with jax.named_scope("attn_out"):
        return o @ _w(lp, "wo", cfg)


def _kda_out(o: jax.Array, u: jax.Array, lp: Params,
             cfg: LingConfig) -> jax.Array:
    """(T, H, D) the rule's output -> W_o (gate_h * rmsnorm_D(o))."""
    return _gated_out(_rms_norm(o.astype(jnp.float32), lp["o_norm"]["scale"],
                                cfg.rms_eps), u, lp, cfg)


def _mla_heads(u: jax.Array, positions: jax.Array, lp: Params,
               cfg: LingConfig):
    """Normed stream (T, E) -> q_n (T, H, nope), q_r (T, H, rope) rotated,
    and the row to cache (T, latent_row) = [rmsnorm(c) | k_r rotated], in
    cfg.dtype."""
    H, nope, rope = cfg.n_head, cfg.qk_nope_dim, cfg.qk_rope_dim
    with jax.named_scope("attn_qkv"):
        q = (u @ _w(lp, "wq", cfg)).reshape(-1, H, nope + rope)
        kva = u @ _w(lp, "wkva", cfg)
        c = _rms_norm(kva[:, :cfg.kv_lora_rank], lp["kv_norm"]["scale"],
                      cfg.rms_eps)
    with jax.named_scope("rope"):
        q_r = rope_pairs(q[..., nope:], positions, cfg.rope_theta)
        k_r = rope_pairs(kva[:, cfg.kv_lora_rank:], positions,
                         cfg.rope_theta)
    return q[..., :nope], q_r, jnp.concatenate(
        [c.astype(cfg.dtype), k_r.astype(cfg.dtype)], axis=-1)


def _up_projection(lp: Params, cfg: LingConfig) -> jax.Array:
    """W_kvb as (kv_lora_rank, H, nope + v): ``[..., :nope]`` is W_uk,
    the rest W_uv."""
    return _w(lp, "wkvb", cfg).reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.qk_nope_dim + cfg.v_head_dim)


def _swiglu(h: jax.Array, ws: Params, cfg: LingConfig) -> jax.Array:
    gate = jax.nn.silu(h @ ws["w1"].astype(cfg.dtype))
    return (gate * (h @ ws["w3"].astype(cfg.dtype))) \
        @ ws["w2"].astype(cfg.dtype)


def _ffn(h: jax.Array, lp: Params, cfg: LingConfig,
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
            cfg.experts_per_token, cfg.route_scale, eps=1e-20,
            n_group=cfg.n_group, topk_group=cfg.topk_group)
    if live is not None:
        idx = choice_of_live_rows(idx, live)
    ex = lp["experts"]
    y, _ = dropless_experts(h, idx, weights, ex["w1"], ex["w3"], ex["w2"],
                            num_experts=cfg.n_experts,
                            first_held=cfg.first_held)
    with jax.named_scope("moe_shared"):
        y = y + _swiglu(h, lp["shared"], cfg)
    return y, idx.astype(jnp.int32)


def _after_mixer(x, m, lp, cfg, live=None):
    """The layer from its mixer's output ``m`` on -> (out, chosen ids or
    None)."""
    x = x + m.astype(x.dtype)
    with jax.named_scope("ln_2"):
        h = _rms_norm(x, lp["norm2"]["scale"], cfg.rms_eps)
    y, ids = _ffn(h, lp, cfg, live)
    return x + y.astype(x.dtype), ids


def _stacked_ids(ids: list, rows: int, cfg: LingConfig) -> jax.Array:
    """(routed layers, rows, k) int32; none routed: no layers."""
    if ids:
        return jnp.stack(ids)
    return jnp.zeros((0, rows, cfg.experts_per_token), jnp.int32)


def _kinds(cfg: LingConfig):
    """Each layer's (leaf name, kind, its number among its kind)."""
    seen = {KDA: 0, MLA: 0}
    for i, kind in enumerate(cfg.mixer_types):
        yield f"l{i:02d}", kind, seen[kind]
        seen[kind] += 1


def _zero_state(cfg: LingConfig) -> Dict[str, jax.Array]:
    return {name: jnp.zeros((cfg.count(KDA),) + s.shape, s.dtype)
            for name, s in recurrent_state(cfg).items()}


# ------------------------------------------------------------------ prefill
def _run(params: Params, tokens: jax.Array, cfg: LingConfig, start, n_total,
         staging: Dict[str, jax.Array], state: Dict[str, jax.Array]):
    """One chunk of one prompt, positions ``start .. start + C - 1``
    (``start`` a multiple of C) of a prompt of ``n_total``, through every
    layer: tokens (C,); ``staging`` as :func:`prefill_staging` says,
    holding every earlier chunk's rows; ``state`` ``{"s": (KDA layers, H,
    D, D), "conv": (KDA layers, (K - 1) 3 H D / 128, 128)}``: what the
    chunk before
    left (ignored at ``start`` 0).  Returns (the stream (C, E), the staging
    with this chunk, the state after the prompt's last position in the
    chunk, the chunk's latent rows a MLA layer, the chosen ids a routed
    layer)."""
    C = tokens.shape[0]
    H, nope, rope = cfg.n_head, cfg.qk_nope_dim, cfg.qk_rope_dim
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(C, dtype=jnp.int32)
    real = positions < n_total
    n_in = jnp.clip(n_total - start, 0, C)
    state = jax.tree.map(lambda s: jnp.where(start == 0, 0.0, s), state)
    # the MLA layers' heads at a width of whole lanes, for the chunk kernel
    wide = -(-(nope + rope) // 128) * 128
    held = jnp.arange(staging["latent"].shape[1] // C, dtype=jnp.int32)
    x = _embed(params, tokens, cfg)
    states, convs, rows, ids = [], [], [], []
    for name, kind, number in _kinds(cfg):
        lp = params["layers"][name]
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
        if kind == KDA:
            with jax.named_scope("attn_qkv"):
                qkv = u @ _w(lp, "wqkv", cfg)
            with jax.named_scope("kda_conv"):
                window = jnp.concatenate(
                    [state["conv"][number].reshape(-1, qkv.shape[1]),
                     qkv.astype(jnp.float32)])
                taps = lp["conv"]["kernel"].astype(jnp.float32)
                y = sum(taps[j] * window[j:j + C]
                        for j in range(cfg.conv_kernel))
                convs.append(lax.dynamic_slice_in_dim(
                    window, n_in, cfg.conv_kernel - 1).reshape(
                        state["conv"].shape[1:]))
            q, k, v, g, beta = _kda_heads(y, u, lp, cfg, real)
            with jax.named_scope("kda_chunk"):
                o, s = delta_rule.kda_chunks(
                    q[None], k[None], v[None], g[None], beta[None],
                    state["s"][number][None], chunk=cfg.kda_chunk)
            states.append(s[0])
            m = _kda_out(o[0], u, lp, cfg)
        else:
            q_n, q_r, row = _mla_heads(u, positions, lp, cfg)
            with jax.named_scope("kv_stage"):
                staging = {"latent": lax.dynamic_update_slice(
                    staging["latent"], row.astype(jnp.float32)[None],
                    (number, start, 0))}
            with jax.named_scope("mla_prefill_attn"):
                staged = staging["latent"][number].astype(cfg.dtype)
                kv = jnp.einsum("pc,chd->phd", staged[:, :cfg.kv_lora_rank],
                                _up_projection(lp, cfg))
                P = staged.shape[0]
                k_r = jnp.broadcast_to(
                    staged[:, None, cfg.kv_lora_rank:], (P, H, rope))
                pad = lambda a, w: jnp.pad(                   # noqa: E731
                    a, ((0, 0), (0, 0), (0, wide - w))).reshape(
                        a.shape[0], H * wide)
                keys = pad(jnp.concatenate([kv[..., :nope], k_r], -1),
                           nope + rope)
                values = pad(kv[..., nope:], cfg.v_head_dim)
                # chunk_attention scales by the width it sees
                q = jnp.concatenate([q_n, q_r], -1) \
                    * math.sqrt(wide / (nope + rope))
                q = jnp.pad(q.astype(cfg.dtype),
                            ((0, 0), (0, 0), (0, wide - nope - rope)))
                o = wattn.chunk_attention(q[:, :, None], keys, values, start,
                                          held)
                o = o[:, :, 0, :cfg.v_head_dim]
            rows.append(row)
            m = _gated_out(o, u, lp, cfg)
        x, chose = _after_mixer(x, m, lp, cfg)
        if chose is not None:
            ids.append(chose)
    state = {"s": jnp.stack(states), "conv": jnp.stack(convs)}
    return x, staging, state, rows, ids


def forward_prefill_chunk(params: Params, tokens: jax.Array, cfg: LingConfig,
                          start, n_total, staging: Dict[str, jax.Array],
                          state: Dict[str, jax.Array],
                          choices: bool = False):
    """One chunk of one prompt: tokens (1, C), its positions ``start ..
    start + C - 1`` of a prompt of ``n_total`` (positions at it and past
    it are padding: they move neither the state nor any real position's
    result).  Returns (logits (1, V) at the prompt's last position where
    this chunk holds it (else at the chunk's first), the staging, the
    state) and, with ``choices``, the experts chosen, (routed layers, C,
    k) int32."""
    x, staging, state, _, ids = _run(params, tokens[0], cfg, start, n_total,
                                     staging, state)
    last = jnp.clip(n_total - 1 - start, 0, tokens.shape[1] - 1)
    x = lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    out = (_logits(params, x, cfg), staging, state)
    return (*out, _stacked_ids(ids, tokens.shape[1], cfg)) if choices \
        else out


def forward_prefill(params: Params, tokens: jax.Array, cfg: LingConfig,
                    last_pos: Optional[jax.Array] = None,
                    choices: bool = False):
    """tokens (B, T) -> (logits, rows, rows, state), each prompt chunk by
    chunk from an empty staging and a zero state: rows (MLA layers, B, T,
    1, latent_row) as they are cached (twice: a latent family's K and V
    are one thing); ``state`` ``{"s": (KDA layers, B, H, D, D), "conv":
    ...}`` at ``last_pos``, or None without it.  With ``choices`` a fifth
    result: the experts chosen, (routed layers, B x T, k) int32.

    ``last_pos`` (traced scalar): logits only at that position, (B, V);
    None returns all of them, (B, T, V)."""
    B, T = tokens.shape
    C = cfg.prefill_chunk
    padded = T + -T % C
    n_total = T if last_pos is None else last_pos + 1
    empty = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         prefill_staging(cfg, padded))
    out = []
    for b in range(B):
        toks = jnp.pad(tokens[b], (0, padded - T))
        staging, state, parts = empty, _zero_state(cfg), []
        for start in range(0, padded, C):
            x, staging, state, _, ids = _run(
                params, toks[start:start + C], cfg, start, n_total, staging,
                state)
            parts.append((x, _stacked_ids(ids, C, cfg)))
        x, ids = (jnp.concatenate(part, axis=axis)[
            (slice(None),) * axis + (slice(0, T),)]
            for part, axis in zip(zip(*parts), (0, 1)))
        x = x if last_pos is None \
            else lax.dynamic_slice_in_dim(x, last_pos, 1, axis=0)[0]
        out.append((_logits(params, x, cfg), staging["latent"][:, :T, None],
                    ids, state))
    logits, rows, ids = (jnp.stack(part, axis=axis) for part, axis in
                         zip(list(zip(*out))[:3], (0, 1, 1)))
    state = None if last_pos is None else jax.tree.map(
        lambda *s: jnp.stack(s, axis=1), *(o[3] for o in out))
    rows = rows.astype(cfg.dtype)
    result = (logits, rows, rows, state)
    if choices:
        result += (ids.reshape(ids.shape[0], B * T, -1),)
    return result


def forward(params: Params, tokens: jax.Array, cfg: LingConfig) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab) float32."""
    return forward_prefill(params, tokens, cfg)[0]


# ------------------------------------------------------------------- decode
def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array, cfg: LingConfig,
                   state: Dict[str, jax.Array], rows: jax.Array,
                   latent_pool: jax.Array, choices: bool = False,
                   live: Optional[jax.Array] = None):
    """One decode step over the engine's latent pool (read-only here, its
    leading axis the MLA layers) and its store of KDA states (leading axis
    the KDA layers).  ``kv_pool`` has no layer and is not read.

    ``state``: ``{"s": (KDA layers, R, H, D, D), "conv": (KDA layers, R,
    (K - 1) 3 H D / 128, 128)}``; ``rows`` (B,) the store row of each batch row (one
    outside the store has none: it reads any and writes nowhere).  Returns
    (logits (B, V) f32, the new latent rows (MLA layers, B, 1,
    latent_row), the same again, the store with the named rows stepped)
    and, with ``choices``, the experts chosen, (routed layers, B, k)
    int32.  ``live`` (B,) bool: the rows that are not padding up to the
    bucket, for the routing."""
    from ray_tpu.ops.paged_attention import latent_attention_decode
    nope, rope, lora = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    store_s, store_c = state["s"], state["conv"]
    x = _embed(params, tokens, cfg)
    new_rows, ids = [], []
    for name, kind, number in _kinds(cfg):
        lp = params["layers"][name]
        with jax.named_scope("ln_1"):
            u = _rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
        if kind == KDA:
            with jax.named_scope("attn_qkv"):
                qkv = u @ _w(lp, "wqkv", cfg)
            with jax.named_scope("kda_conv"):
                y, store_c = ssm.conv_step_rows(
                    store_c, number, rows, qkv, lp["conv"]["kernel"])
            q, k, v, g, beta = _kda_heads(y, u, lp, cfg)
            with jax.named_scope("kda_step"):
                o, store_s = delta_rule.kda_step_rows(
                    store_s, number, rows, q, k, v, g, beta)
            m = _kda_out(o, u, lp, cfg)
        else:
            q_n, q_r, row = _mla_heads(u, positions, lp, cfg)
            w_kvb = _up_projection(lp, cfg)
            with jax.named_scope("mla_absorb"):
                q_c = jnp.einsum("bhn,chn->bhc", q_n, w_kvb[..., :nope])
                q_abs = jnp.concatenate([q_c, q_r], axis=-1) \
                    * (1.0 / math.sqrt(nope + rope))
            with jax.named_scope("mla_latent_attn"):
                o_c = latent_attention_decode(
                    q_abs.astype(cfg.dtype), latent_pool, number,
                    block_tables, ctx_lens, row, lora)
            with jax.named_scope("mla_absorb"):
                o = jnp.einsum("bhc,chv->bhv", o_c.astype(cfg.dtype),
                               w_kvb[..., nope:])
            new_rows.append(row[:, None])
            m = _gated_out(o, u, lp, cfg)
        x, chose = _after_mixer(x, m, lp, cfg, live)
        if chose is not None:
            ids.append(chose)
    new_rows = jnp.stack(new_rows)
    out = (_logits(params, x, cfg), new_rows, new_rows,
           {"s": store_s, "conv": store_c})
    return (*out, _stacked_ids(ids, tokens.shape[0], cfg)) if choices \
        else out
