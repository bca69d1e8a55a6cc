"""The Qwen3-Next decoder (``model_type`` ``qwen3_next``), for training:
three Gated DeltaNet layers to one gated full-attention layer, each
followed by softmax-routed experts beside a gated shared one.

Layer ``i`` is full attention where ``(i + 1) % attn_interval == 0``, else
Gated DeltaNet.  Per layer, with x the residual stream and ``rms0`` the
zero-centred norm ``x rsqrt(mean x^2 + eps) (1 + w)``::

    u = rms0(x)
    Gated DeltaNet:
        [q | k | v | z] = W_qkvz u      q, k: G heads of dk; v, z: H = G R of dv
        [b | a] = W_ba u                 one each a value head
        [q | k | v] = silu(causal depthwise conv_4([q | k | v]))   no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   float32
        q = l2norm(q) / sqrt(dk);  k = l2norm(k)
        o = gated delta rule(q, k, v, g, beta)      ops/delta_rule.py; value
                                                    head h reads key head h // R
        x = x + W_out (w . o rsqrt(mean o^2 + eps) . silu(z))   a head's dv
    full attention:
        [q_h | gate_h] = W_q u          H heads of [D | D]
        k, v = W_k u, W_v u             KV heads of D
        q, k = rms0(q), rms0(k)         over D
        RoPE on the first ``rotary_dim`` lanes of a head, by halves
        a_h = softmax(q_h k^T / sqrt(D), causal) v      KV head h // (H / KV)
        x = x + W_o (a . sigmoid(gate))
    n = rms0(x)
    p = softmax(W_r n) over ALL routed experts; the k chosen are its top;
        w_e = p_e / sum of the chosen p
    x = x + sum over chosen, held e of w_e Expert_e(n)
          + sigmoid(W_sg n) Shared(n)

The layer is told which experts it holds (``n_held_experts`` from
``first_held_expert``, of the ``n_routed_experts`` the router chooses
among): it routes over all of them, weighs by the weight normalised over
all k chosen, held or not, and adds its own experts' part
(``ops/moe.dropless_experts``; DESIGN.md, held experts).  The vocabulary
may likewise be this chip's rows.

Layout: two stacks of block leaves, ``gdn_blocks`` and ``attn_blocks``,
each on a leading layer axis in the published order of their kind; a
``lax.scan`` over the periods runs a scan over a period's DeltaNet
blocks and then its attention block, so each kind of block is compiled
once.  ``in_proj_qkvz`` holds its columns as [q | k | v | z] and
``in_proj_ba`` as [b | a], heads in order (the published checkpoint
groups both by key head: [q_g | k_g | v_g | z_g] and [b_g | a_g]);
``wq`` keeps the published [query | gate] a head.  ``A_log`` and
``dt_bias`` are float32 whatever the parameters are.  The serving
forwards (rows of state and conv tails in the cache) are not written.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models._common import (  # noqa: F401
    _gqa_expand, _rope, experts_in_place, next_token_nll, normal_init,
    remat_block, split_batch)

Params = Dict[str, Any]


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936         # the rows held here
    max_positions: int = 262144
    n_embd: int = 2048
    n_layer: int = 48                # whole periods of attn_interval
    attn_interval: int = 4           # full_attention_interval
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    rotary_dim: int = 64             # head_dim x partial_rotary_factor
    rope_theta: float = 1e7
    gdn_key_heads: int = 16          # linear_num_key_heads
    gdn_value_heads: int = 32        # linear_num_value_heads
    gdn_key_dim: int = 128           # linear_key_head_dim
    gdn_value_dim: int = 128         # linear_value_head_dim
    conv_kernel: int = 4             # linear_conv_kernel_dim
    rule_chunk: int = 64             # positions a chunk of the delta rule
    expert_dim: int = 512            # moe_intermediate_size
    shared_dim: int = 512            # shared_expert_intermediate_size
    n_routed_experts: int = 512      # the router's width
    n_held_experts: int = 512        # of them, held by this chip ...
    first_held_expert: int = 0       # ... from this one on
    experts_per_token: int = 10
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"       # full | attn (_common.remat_block)
    attn_impl: str = "auto"          # auto | dense | flash

    def __post_init__(self):
        if self.n_layer % self.attn_interval:
            raise ValueError(f"n_layer {self.n_layer} is no whole number of "
                             f"periods of {self.attn_interval}")

    @property
    def n_period(self) -> int:
        return self.n_layer // self.attn_interval

    @property
    def gdn_key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def gdn_value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_width(self) -> int:
        return 2 * self.gdn_key_width + self.gdn_value_width


def tiny(vocab: int = 200, seq: int = 48, **changes) -> Qwen3NextConfig:
    """The decoder at a test's size: two periods (a state crosses a full
    attention layer), 2 key and 4 value heads, 2 / 1 attention heads with
    half their lanes rotated, 8 routed experts of which 4 are held, 3 a
    token, chunks of 8."""
    return Qwen3NextConfig(**{**dict(
        vocab_size=vocab, max_positions=seq, n_embd=64, n_layer=8,
        n_head=2, n_kv_head=1, head_dim=16, rotary_dim=8, gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=12, rule_chunk=8,
        expert_dim=24, shared_dim=20, n_routed_experts=8, n_held_experts=4,
        first_held_expert=0, experts_per_token=3), **changes})


# ------------------------------------------------------------------- params
def init_params(rng: jax.Array, cfg: Qwen3NextConfig) -> Params:
    """Every matrix normal at 0.02 (the family's ``initializer_range``);
    zero-centred norm weights 0 and the DeltaNet output norm's 1; the conv
    uniform in +-1/sqrt(taps); ``A_log`` the log of uniform (0, 16) and
    ``dt_bias`` the inverse softplus of a step drawn log-uniform in (0.001,
    0.1), as Gated DeltaNet and Mamba-2 draw them.  Each stacked leaf is
    drawn in one call."""
    pd = cfg.param_dtype
    E, F, X, Xh = (cfg.n_embd, cfg.expert_dim, cfg.n_routed_experts,
                   cfg.n_held_experts)
    k = iter(jax.random.split(rng, 40))

    def moe(L):
        def swiglu(width):
            return {"w_gate": {"kernel": normal_init(next(k), (L, E, width), pd)},
                    "w_up": {"kernel": normal_init(next(k), (L, E, width), pd)},
                    "w_down": {"kernel": normal_init(next(k), (L, width, E), pd)}}
        return {
            "mixer_norm": {"scale": jnp.zeros((L, E), pd)},
            "mlp_norm": {"scale": jnp.zeros((L, E), pd)},
            "router": {"kernel": normal_init(next(k), (L, E, X), pd)},
            "experts": {"w_gate": normal_init(next(k), (L, Xh, E, F), pd),
                        "w_up": normal_init(next(k), (L, Xh, E, F), pd),
                        "w_down": normal_init(next(k), (L, Xh, F, E), pd)},
            "shared": swiglu(cfg.shared_dim),
            "shared_gate": {"kernel": normal_init(next(k), (L, E, 1), pd)},
        }

    La = cfg.n_period
    Lg = cfg.n_layer - La
    Hv, taps = cfg.gdn_value_heads, cfg.conv_kernel
    step = jnp.exp(jax.random.uniform(next(k), (Lg, Hv), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    gdn = {
        **moe(Lg),
        "in_proj_qkvz": {"kernel": normal_init(
            next(k), (Lg, E, cfg.conv_width + cfg.gdn_value_width), pd)},
        "in_proj_ba": {"kernel": normal_init(next(k), (Lg, E, 2 * Hv), pd)},
        "conv": {"kernel": jax.random.uniform(
            next(k), (Lg, taps, cfg.conv_width), jnp.float32,
            -taps ** -0.5, taps ** -0.5).astype(pd)},
        "A_log": jnp.log(jax.random.uniform(next(k), (Lg, Hv), jnp.float32,
                                            1e-3, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "out_norm": {"scale": jnp.ones((Lg, cfg.gdn_value_dim), pd)},
        "out_proj": {"kernel": normal_init(
            next(k), (Lg, cfg.gdn_value_width, E), pd)},
    }
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    attn = {
        **moe(La),
        "wq": {"kernel": normal_init(next(k), (La, E, H * 2 * D), pd)},
        "wk": {"kernel": normal_init(next(k), (La, E, KV * D), pd)},
        "wv": {"kernel": normal_init(next(k), (La, E, KV * D), pd)},
        "q_norm": {"scale": jnp.zeros((La, D), pd)},
        "k_norm": {"scale": jnp.zeros((La, D), pd)},
        "wo": {"kernel": normal_init(next(k), (La, H * D, E), pd)},
    }
    return {
        "wte": normal_init(next(k), (cfg.vocab_size, E), pd),
        "gdn_blocks": gdn,
        "attn_blocks": attn,
        "norm_f": {"scale": jnp.zeros((E,), pd)},
        "lm_head": {"kernel": normal_init(next(k), (E, cfg.vocab_size), pd)},
    }


# ------------------------------------------------------------------ forward
def _rms0(x, weight, eps):
    """The zero-centred RMS norm: the weight is stored as its distance
    from 1."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def _gdn_mixer(u: jax.Array, lp: Params, cfg: Qwen3NextConfig):
    """Normed hidden states (B, T, E) -> (W_out . gated delta rule
    (B, T, E), the mean decay exp(g) of the layer, the rule's last state
    (B, H, dk, dv) float32, which training drops)."""
    from ray_tpu.ops.delta_rule import gated_delta_rule_qkv
    from ray_tpu.ops.ssm import causal_conv_silu, gated_rms_norm
    B, T, _ = u.shape
    G, H = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    with jax.named_scope("gdn_in"):
        # the conv's channels and the gate are read by column group: a
        # slice of the weight, not of 16,384 rows of activations
        w = lp["in_proj_qkvz"]["kernel"].astype(cfg.dtype)
        qkv = u @ w[:, :cfg.conv_width]
        z = u @ w[:, cfg.conv_width:]
        ba = jnp.dot(u, lp["in_proj_ba"]["kernel"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    with jax.named_scope("gdn_conv"):
        qkv = causal_conv_silu(qkv, lp["conv"]["kernel"])
    with jax.named_scope("gdn_rule"):
        beta = jax.nn.sigmoid(ba[..., :H])
        g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., H:] + lp["dt_bias"].astype(jnp.float32))
        # q | k | v where the conv left them, q and k not yet normed: the
        # rule's kernels read (B, T, heads x 128) by column blocks and
        # norm what they load
        o, state = gated_delta_rule_qkv(qkv, g, beta, G, dk,
                                        chunk=cfg.rule_chunk)
    with jax.named_scope("gdn_norm"):
        o = gated_rms_norm(o, z, lp["out_norm"]["scale"], cfg.rms_eps)
    with jax.named_scope("gdn_out"):
        out = o.reshape(B, T, H * dv) \
            @ lp["out_proj"]["kernel"].astype(cfg.dtype)
    return out, jnp.exp(g).mean(), state


def _partial_rope(x: jax.Array, rotary_dim: int, theta: float) -> jax.Array:
    """Rotary embedding on the first ``rotary_dim`` lanes of (B, T, H, D)
    by halves (pairs (i, i + rotary_dim / 2)); the other lanes pass."""
    return jnp.concatenate([_rope(x[..., :rotary_dim], theta),
                            x[..., rotary_dim:]], -1)


def _attention(u: jax.Array, lp: Params, cfg: Qwen3NextConfig) -> jax.Array:
    """Normed hidden states (B, T, E) -> W_o (attention . sigmoid(gate))."""
    from ray_tpu.ops.attention import causal_attention
    B, T, E = u.shape
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        # query and gate by column group of W_q, as above
        wq = lp["wq"]["kernel"].astype(cfg.dtype).reshape(E, H, 2, D)
        q = jnp.einsum("bte,ehd->bthd", u, wq[:, :, 0])
        gate = jnp.einsum("bte,ehd->bthd", u, wq[:, :, 1])
        k = (u @ lp["wk"]["kernel"].astype(cfg.dtype)).reshape(B, T, KV, D)
        v = (u @ lp["wv"]["kernel"].astype(cfg.dtype)).reshape(B, T, KV, D)
    with jax.named_scope("qk_norm"):
        q = _rms0(q, lp["q_norm"]["scale"], cfg.rms_eps)
        k = _rms0(k, lp["k_norm"]["scale"], cfg.rms_eps)
    with jax.named_scope("rope"):
        q = _partial_rope(q, cfg.rotary_dim, cfg.rope_theta)
        k = _partial_rope(k, cfg.rotary_dim, cfg.rope_theta)
    with jax.named_scope("attn"):
        a = causal_attention(q, _gqa_expand(k, H), _gqa_expand(v, H),
                             impl=cfg.attn_impl)
    with jax.named_scope("attn_gate"):
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
    with jax.named_scope("attn_out"):
        return a.reshape(B, T, H * D) @ lp["wo"]["kernel"].astype(cfg.dtype)


def _swiglu(h: jax.Array, lp: Params, cfg: Qwen3NextConfig) -> jax.Array:
    gate = jax.nn.silu(h @ lp["w_gate"]["kernel"].astype(cfg.dtype))
    up = h @ lp["w_up"]["kernel"].astype(cfg.dtype)
    return (gate * up) @ lp["w_down"]["kernel"].astype(cfg.dtype)


def _experts(h: jax.Array, lp: Params, cfg: Qwen3NextConfig,
             stack: Optional[tuple] = None):
    """Normed hidden states (B, T, E) -> (the held routed experts' part
    plus the gated shared expert, HeldStats).  ``stack``:
    (``_common.experts_in_place`` of the layer's stack, its index in it,
    traced), beside ``lp``'s own slice."""
    from ray_tpu.ops.moe import HeldStats, dropless_moe_ffn
    with jax.named_scope("moe"):
        ex = lp["experts"]
        routed, stats = dropless_moe_ffn(
            h.reshape(-1, h.shape[-1]), lp["router"]["kernel"], ex["w_gate"],
            ex["w_up"], ex["w_down"], k=cfg.experts_per_token,
            scoring="softmax", norm_topk=True,
            first_held=cfg.first_held_expert, stack=stack)
        if not isinstance(stats, HeldStats):     # every expert is held
            rows = h.size // h.shape[-1] * cfg.experts_per_token
            stats = HeldStats(jnp.float32(rows), stats.load_max_over_mean,
                              jnp.float32(1.0), jnp.float32(1.0))
        with jax.named_scope("shared"):
            open_ = jax.nn.sigmoid(jnp.dot(
                h, lp["shared_gate"]["kernel"].astype(cfg.dtype),
                preferred_element_type=jnp.float32))
            shared = (_swiglu(h, lp["shared"], cfg).astype(jnp.float32)
                      * open_).astype(cfg.dtype)
    return routed.reshape(h.shape) + shared, stats


def _block(x: jax.Array, lp: Params, cfg: Qwen3NextConfig, kind: str,
           stack: Optional[tuple] = None):
    """One decoder block -> (out, (HeldStats, mean decay | None)).  The
    norms run under GPT-2's scope names (ln_1, ln_2; models/gpt2.py)."""
    with jax.named_scope("ln_1"):
        u = _rms0(x, lp["mixer_norm"]["scale"], cfg.rms_eps)
    if kind == "gdn":
        with jax.named_scope("gdn"):
            mixed, decay, _ = _gdn_mixer(u, lp, cfg)
    else:
        mixed, decay = _attention(u, lp, cfg), None
    x = x + mixed
    with jax.named_scope("ln_2"):
        h = _rms0(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
    f, stats = _experts(h, lp, cfg, stack)
    return x + f, (stats, decay)


def forward_hidden(params: Params, tokens: jax.Array, cfg: Qwen3NextConfig):
    """tokens (B, T) int32 -> (final-norm hidden states (B, T, E) in
    cfg.dtype, (the DeltaNet layers' (HeldStats, mean decay) stacked
    (periods, interval - 1), the attention layers' HeldStats stacked
    (periods,)))."""
    with jax.named_scope("embed"):
        x = params["wte"].astype(cfg.dtype)[tokens]

    def block_of(kind):
        # the kernels read a layer's experts in its stack, in place
        whole = experts_in_place(params[f"{kind}_blocks"]["experts"])
        return lambda x, xs: _block(x, xs[0], cfg, kind, (whole, xs[1]))

    blocks = {kind: block_of(kind) for kind in ("gdn", "attn")}
    if cfg.remat:
        # the attention block as the other decoders' (``attn`` keeps the
        # flash kernel's output and lse); a DeltaNet block has none of
        # those names and under ``attn`` keeps what is its costliest to
        # make again, the rule's solved (C, C) systems
        from ray_tpu.ops.attention import flash_runs
        from ray_tpu.ops.delta_rule import INVERSE
        blocks["attn"] = remat_block(
            blocks["attn"], cfg.remat_policy,
            flash_runs(tokens.shape[1], cfg.attn_impl))
        blocks["gdn"] = jax.checkpoint(
            blocks["gdn"], policy=None if cfg.remat_policy == "full" else
            jax.checkpoint_policies.save_only_these_names(INVERSE))
    gdn = jax.tree_util.tree_map(
        lambda a: a.reshape(cfg.n_period, cfg.attn_interval - 1,
                            *a.shape[1:]), params["gdn_blocks"])

    def period(x, xs):
        lps, at = xs
        x, gdn_stats = lax.scan(blocks["gdn"], x, (lps[0], at[0]))
        x, (attn_stats, _) = blocks["attn"](x, (lps[1], at[1]))
        return x, (gdn_stats, attn_stats)

    layers = (jnp.arange(cfg.n_layer - cfg.n_period).reshape(
        cfg.n_period, -1), jnp.arange(cfg.n_period))
    x, stats = lax.scan(period, x, ((gdn, params["attn_blocks"]), layers))
    with jax.named_scope("ln_f"):
        return _rms0(x, params["norm_f"]["scale"], cfg.rms_eps), stats


def forward(params: Params, tokens: jax.Array,
            cfg: Qwen3NextConfig) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab held) f32."""
    x, _ = forward_hidden(params, tokens, cfg)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
        return logits.astype(jnp.float32)


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: Qwen3NextConfig) -> jax.Array:
    """Mean next-token cross entropy over the vocabulary rows held, a
    scalar; no auxiliary term (the source's ``output_router_logits`` is
    off).  Hands what the held experts saw and how fast the DeltaNet
    states forget to the train step's metrics
    (spmd.report_step_metrics)."""
    inp, tgt = split_batch(batch)
    x, ((gdn_held, decay), attn_held) = forward_hidden(params, inp, cfg)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
    from ray_tpu.parallel.spmd import report_step_metrics
    held = jax.tree_util.tree_map(
        lambda g, a: jnp.concatenate([g.reshape(-1), a.reshape(-1)]),
        gdn_held, attn_held)
    report_step_metrics(
        moe_held_rows=held.held_rows.mean(),
        moe_held_load_max_over_mean=held.load_max_over_mean.max(),
        moe_choice_share_held=held.choice_share_held.mean(),
        moe_tile_fill=held.tile_fill.mean(),
        gdn_decay_mean=decay.mean())
    return next_token_nll(logits, tgt)
