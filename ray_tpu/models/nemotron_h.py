"""The Nemotron-H decoder (``model_type`` ``nemotron_h``; NVIDIA, arXiv:
2504.03624; Nemotron-3-Nano-30B-A3B), for training: a stack in which
every layer is ONE mixer, of three kinds, in an order that has no period.

``pattern`` (the published ``hybrid_override_pattern``) names each
layer's kind: ``M`` a Mamba-2 mixer, ``E`` routed experts beside a shared
one, ``*`` grouped-query attention.  With ``N(x) = x rsqrt(mean x^2 +
eps) g`` and ``u = N(x)``, every layer is ``x <- x + mixer(u)``::

    M   [z | xBC | dt] = W_in u               H heads of P; G groups of N
        xBC = silu(conv_K(xBC) + b)           depthwise, causal, with bias
        [x | B | C] = xBC                     head h reads group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)        a head each
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t         (P, N) float32
        y_t = S_t C_t + D x_t
        m = W_out (N_group(y silu(z)) w)      the gate first, then RMS over
                                              each of the G groups of H P / G
    *   q = W_q u (H x D);  k, v = W_k u, W_v u (KV x D)     NO rotary
        o = softmax(q k^T / sqrt(D), causal) v               KV head h // (H / KV)
        m = W_o o
    E   s = sigmoid(W_r u), float32, over ALL routed experts
        chosen = the k largest of s + select_bias            (no group limit)
        w_e = scale s_e / (sum of the chosen s + 1e-20)
        m = sum over chosen, held e of w_e W_down,e relu(W_up,e u)^2
            + W_down,sh relu(W_up,sh u)^2                    no gate matrix
    logits = W_head N(x)

The attention applies no position: the family's Mamba layers carry it
(the published ``NemotronHAttention`` builds no rotary embedding).  The
layer is told which experts it holds (``n_held_experts`` from
``first_held_expert``, of the ``n_routed_experts`` the router chooses
among): it routes over all of them and adds its own experts' part
(``ops/moe.dropless_experts`` in its two-matrix form; DESIGN.md, held
experts).  The vocabulary may likewise be this chip's rows.

Layout: three stacks of block leaves, ``mamba_blocks``, ``expert_blocks``
and ``attn_blocks``, each on a leading layer axis in the published order
of its kind.  The layers run in the pattern's order by a loop that takes
layer ``j`` of its kind's stack (a pattern without a period has no scan
over periods: ``qwen3_next.py``); the grouped matmuls read a layer's
experts in their stack in place (``_common.experts_in_place``).
``in_proj`` holds its columns [z | x | B | C | dt] as published.
``A_log``, ``D``, ``dt_bias`` and ``select_bias`` are float32 whatever the
parameters are; the scan's state and decays are float32 (``ops/ssm.py``).
The serving forwards (rows of state, conv tails, a chunk entry point) are
not written.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models._common import (  # noqa: F401
    _gqa_expand, _rms_norm, experts_in_place, next_token_nll, normal_init,
    remat_block, split_batch)

Params = Dict[str, Any]

KINDS = {"M": "mamba", "E": "expert", "*": "attn"}
NANO_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072         # the rows held here
    max_positions: int = 262144
    n_embd: int = 2688
    pattern: str = NANO_PATTERN      # hybrid_override_pattern: M | E | *
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    ssm_heads: int = 64              # mamba_num_heads
    ssm_head_dim: int = 64           # mamba_head_dim (not expand x n_embd)
    ssm_state: int = 128             # ssm_state_size: columns of a state
    ssm_groups: int = 8              # n_groups: heads share B and C
    conv_kernel: int = 4
    ssm_chunk: int = 128             # chunk_size
    expert_dim: int = 1856           # moe_intermediate_size
    shared_dim: int = 3712           # moe_shared_expert_intermediate_size
    n_routed_experts: int = 128      # the router's width
    n_held_experts: int = 128        # of them, held by this chip ...
    first_held_expert: int = 0       # ... from this one on
    experts_per_token: int = 6
    routed_scale: float = 2.5        # routed_scaling_factor
    rms_eps: float = 1e-5
    # the depth ``rescale_prenorm_residual`` divides by: the whole model's
    # where ``pattern`` is one stage of it; None: this pattern's
    init_depth: Optional[int] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"       # full | attn (_common.remat_block)
    attn_impl: str = "auto"          # auto | dense | flash

    def __post_init__(self):
        other = sorted(set(self.pattern) - set(KINDS))
        if "-" in other:
            raise ValueError(
                f"pattern {self.pattern!r} has a dense MLP layer ('-'), "
                "which models/nemotron_h.py does not have: M | E | *")
        if other or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: a layer is one of "
                             f"{sorted(KINDS)}, not {other}")

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        """Layers of ``kind`` (M | E | *)."""
        return self.pattern.count(kind)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: x and the groups' B and C."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state


def tiny(vocab: int = 200, seq: int = 48, **changes) -> NemotronHConfig:
    """The stack at a test's size: the cell's nine layers in their order,
    4 mixer heads of 6 on 2 groups of 8 state columns, chunks of 8, 4 / 2
    attention heads of 8, 8 routed experts of which 4 are held, 3 a
    token."""
    return NemotronHConfig(**{**dict(
        vocab_size=vocab, max_positions=seq, n_embd=48, pattern="MEMEM*EME",
        n_head=4, n_kv_head=2, head_dim=8, ssm_heads=4, ssm_head_dim=6,
        ssm_state=8, ssm_groups=2, ssm_chunk=8, expert_dim=20, shared_dim=28,
        n_routed_experts=8, n_held_experts=4, first_held_expert=0,
        experts_per_token=3), **changes})


# ------------------------------------------------------------------- params
def init_params(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """As the release's ``_init_weights`` leaves a fresh model: every
    matrix uniform in +-1/sqrt(fan_in) (torch's ``Linear``, which it does
    not touch), the embedding and the router normal at 0.02, the Mamba
    out-projection divided by sqrt(depth) (``rescale_prenorm_residual``),
    the conv and its bias uniform in +-1/sqrt(taps), norm scales 1,
    ``select_bias`` 0.  The recurrence's ``A`` uniform in (1, 16), ``dt``
    log-uniform in (0.001, 0.1) with the floor 1e-4 and ``dt_bias`` its
    inverse softplus, ``D`` ones, as Mamba-2 draws them.  Each stacked
    leaf is drawn in one call."""
    pd, f32 = cfg.param_dtype, jnp.float32
    E, F, Fs = cfg.n_embd, cfg.expert_dim, cfg.shared_dim
    X, Xh = cfg.n_routed_experts, cfg.n_held_experts
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    Lm, Le, La = cfg.count("M"), cfg.count("E"), cfg.count("*")
    k = iter(jax.random.split(rng, 24))

    def linear(*shape, fan_in, scale=1.0):
        bound = scale / math.sqrt(fan_in)
        return jax.random.uniform(next(k), shape, f32, -bound, bound) \
            .astype(pd)

    def norm(L):
        return {"scale": jnp.ones((L, E), pd)}

    taps = cfg.conv_kernel
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        next(k), (Lm, cfg.ssm_heads), f32, math.log(1e-3), math.log(1e-1))),
        1e-4)
    mamba = {
        "norm": norm(Lm),
        "in_proj": {"kernel": linear(
            Lm, E, cfg.d_ssm + cfg.conv_dim + cfg.ssm_heads, fan_in=E)},
        "conv": {"kernel": linear(Lm, taps, cfg.conv_dim, fan_in=taps),
                 "bias": linear(Lm, cfg.conv_dim, fan_in=taps)},
        "A_log": jnp.log(jax.random.uniform(next(k), (Lm, cfg.ssm_heads),
                                            f32, 1.0, 16.0)),
        "D": jnp.ones((Lm, cfg.ssm_heads), f32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "ssm_norm": {"scale": jnp.ones((Lm, cfg.d_ssm), pd)},
        "out_proj": {"kernel": linear(
            Lm, cfg.d_ssm, E, fan_in=cfg.d_ssm,
            scale=(cfg.init_depth or cfg.n_layer) ** -0.5)},
    }
    expert = {
        "norm": norm(Le),
        "router": {"kernel": normal_init(next(k), (Le, E, X), pd),
                   "select_bias": jnp.zeros((Le, X), f32)},
        "experts": {"w_up": linear(Le, Xh, E, F, fan_in=E),
                    "w_down": linear(Le, Xh, F, E, fan_in=F)},
        "shared": {"w_up": {"kernel": linear(Le, E, Fs, fan_in=E)},
                   "w_down": {"kernel": linear(Le, Fs, E, fan_in=Fs)}},
    }
    attn = {
        "norm": norm(La),
        "wq": {"kernel": linear(La, E, H * D, fan_in=E)},
        "wk": {"kernel": linear(La, E, KV * D, fan_in=E)},
        "wv": {"kernel": linear(La, E, KV * D, fan_in=E)},
        "wo": {"kernel": linear(La, H * D, E, fan_in=H * D)},
    }
    return {
        "wte": normal_init(next(k), (cfg.vocab_size, E), pd),
        "mamba_blocks": mamba,
        "expert_blocks": expert,
        "attn_blocks": attn,
        "norm_f": {"scale": jnp.ones((E,), pd)},
        "lm_head": {"kernel": linear(E, cfg.vocab_size, fan_in=E)},
    }


# ------------------------------------------------------------------ mixers
def _mamba(u: jax.Array, lp: Params, cfg: NemotronHConfig):
    """Normed hidden states (B, T, E) -> (W_out . the Mamba-2 mixer (B, T,
    E), the layer's mean decay exp(dt A)), from a zero state.  The
    mixer's parts run under Falcon-H1's scope names (ssm_*)."""
    from ray_tpu.ops import ssm
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        # z, the conv's channels and dt by column group: a slice of the
        # weight, not of 16,384 rows of activations
        w = lp["in_proj"]["kernel"].astype(cfg.dtype)
        z = u @ w[:, :cfg.d_ssm]
        xbc = u @ w[:, cfg.d_ssm:cfg.d_ssm + cfg.conv_dim]
        dt = jnp.dot(u, w[:, cfg.d_ssm + cfg.conv_dim:],
                     preferred_element_type=f32)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(ssm.causal_conv(xbc, lp["conv"]["kernel"],
                                          lp["conv"]["bias"])[0])
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
        a = -jnp.exp(lp["A_log"].astype(f32))
        # x | B | C read where the conv left them, and D x added there
        y = ssm.ssd_scan_train(xbc, dt, a, lp["D"], cfg.ssm_head_dim,
                               cfg.ssm_groups, cfg.ssm_chunk)
        decay = jnp.exp(dt * a).mean()
    with jax.named_scope("ssm_norm"):
        g = ssm.gate_then_group_norm(y, z.astype(f32),
                                     lp["ssm_norm"]["scale"], cfg.ssm_groups,
                                     cfg.rms_eps, cfg.dtype)
    with jax.named_scope("ssm_out"):
        return g @ lp["out_proj"]["kernel"].astype(cfg.dtype), decay


def _attention(u: jax.Array, lp: Params, cfg: NemotronHConfig) -> jax.Array:
    """Normed hidden states (B, T, E) -> W_o . attention, no position
    applied to q or k."""
    from ray_tpu.ops.attention import causal_attention
    B, T, _ = u.shape
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        q = (u @ lp["wq"]["kernel"].astype(cfg.dtype)).reshape(B, T, H, D)
        k = (u @ lp["wk"]["kernel"].astype(cfg.dtype)).reshape(B, T, KV, D)
        v = (u @ lp["wv"]["kernel"].astype(cfg.dtype)).reshape(B, T, KV, D)
    with jax.named_scope("attn"):
        o = causal_attention(q, _gqa_expand(k, H), _gqa_expand(v, H),
                             impl=cfg.attn_impl)
    with jax.named_scope("attn_out"):
        return o.reshape(B, T, H * D) @ lp["wo"]["kernel"].astype(cfg.dtype)


def _relu2(h: jax.Array, lp: Params, cfg: NemotronHConfig) -> jax.Array:
    up = jax.nn.relu(h @ lp["w_up"]["kernel"].astype(cfg.dtype))
    return (up * up) @ lp["w_down"]["kernel"].astype(cfg.dtype)


def _experts(u: jax.Array, lp: Params, cfg: NemotronHConfig,
             stack: Optional[tuple] = None):
    """Normed hidden states (B, T, E) -> (the held routed experts' part
    plus the shared expert, HeldStats).  ``stack``:
    (``_common.experts_in_place`` of the expert stack, this layer's index
    in it), beside ``lp``'s own slice."""
    from ray_tpu.ops.moe import dropless_moe_ffn
    with jax.named_scope("moe"):
        ex, router = lp["experts"], lp["router"]
        routed, stats = dropless_moe_ffn(
            u.reshape(-1, u.shape[-1]), router["kernel"], None, ex["w_up"],
            ex["w_down"], k=cfg.experts_per_token, scoring="sigmoid",
            select_bias=router["select_bias"], weight_scale=cfg.routed_scale,
            first_held=cfg.first_held_expert, stack=stack)
        with jax.named_scope("shared"):
            shared = _relu2(u, lp["shared"], cfg)
    return routed.reshape(u.shape) + shared, stats


def _block(x: jax.Array, lp: Params, cfg: NemotronHConfig, kind: str,
           stack: Optional[tuple] = None):
    """One layer -> (x + mixer(N(x)), the mean decay of an M layer | the
    HeldStats of an E layer | None).  The norm runs under GPT-2's scope
    name ln_1 (models/gpt2.py): a layer has no second one."""
    with jax.named_scope("ln_1"):
        u = _rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
    if kind == "M":
        with jax.named_scope("ssm"):
            mixed, told = _mamba(u, lp, cfg)
    elif kind == "E":
        mixed, told = _experts(u, lp, cfg, stack)
    else:
        mixed, told = _attention(u, lp, cfg), None
    return x + mixed, told


def forward_hidden(params: Params, tokens: jax.Array, cfg: NemotronHConfig):
    """tokens (B, T) int32 -> (final-norm hidden states (B, T, E) in
    cfg.dtype, {"M": the M layers' mean decays, "E": the E layers'
    HeldStats}, each stacked on a leading axis in its kind's order)."""
    with jax.named_scope("embed"):
        x = params["wte"].astype(cfg.dtype)[tokens]
    # the kernels read a layer's experts in the stack, in place
    whole = experts_in_place(params["expert_blocks"]["experts"])
    told = {kind: [] for kind in KINDS}
    for kind in cfg.pattern:
        at = len(told[kind])
        block = partial(_block, cfg=cfg, kind=kind, stack=(whole, at))
        if cfg.remat and kind == "*":
            from ray_tpu.ops.attention import flash_runs
            block = remat_block(block, cfg.remat_policy,
                                flash_runs(tokens.shape[1], cfg.attn_impl))
        elif cfg.remat:
            # an M or E block has none of the names ``attn`` keeps: it is
            # made again whole from its input
            block = remat_block(block, "full", False)
        x, said = block(x, jax.tree_util.tree_map(
            lambda a: a[at], params[f"{KINDS[kind]}_blocks"]))
        told[kind].append(said)
    stacked = {kind: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *said)
               for kind, said in told.items() if said and kind != "*"}
    with jax.named_scope("ln_f"):
        return _rms_norm(x, params["norm_f"]["scale"], cfg.rms_eps), stacked


def forward(params: Params, tokens: jax.Array,
            cfg: NemotronHConfig) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab held) f32."""
    x, _ = forward_hidden(params, tokens, cfg)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
        return logits.astype(jnp.float32)


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: NemotronHConfig) -> jax.Array:
    """Mean next-token cross entropy over the vocabulary rows held, a
    scalar; no auxiliary term (the bias balances).  Hands what the held
    experts saw and how fast the Mamba states forget to the train step's
    metrics (spmd.report_step_metrics)."""
    inp, tgt = split_batch(batch)
    x, told = forward_hidden(params, inp, cfg)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
    from ray_tpu.parallel.spmd import report_step_metrics
    said = {}
    if "E" in told:
        held = told["E"]
        said.update(
            moe_held_rows=held.held_rows.mean(),
            moe_held_load_max_over_mean=held.load_max_over_mean.max(),
            moe_choice_share_held=held.choice_share_held.mean(),
            moe_tile_fill=held.tile_fill.mean())
    if "M" in told:
        said.update(ssm_decay_mean=told["M"].mean())
    report_step_metrics(**said)
    return next_token_nll(logits, tgt)
