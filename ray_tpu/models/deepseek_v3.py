"""The DeepSeek-V3 decoder block (``model_type`` ``deepseek_v3``), for
training: multi-head latent attention, a leading dense layer, then layers
of routed experts beside a shared one behind a sigmoid router.

Per layer, with x the residual stream::

    u = RMSNorm(x)
    q = W_q u                         H heads of [nope | rope] (no q latent)
    [c | k_rope] = W_kva u            one latent and ONE rotary key a token
    [k_nope_h | v_h] = W_kvb RMSNorm(c)
    RoPE on interleaved pairs (2i, 2i + 1) of q_rope_h and of k_rope
    a_h = softmax(q_h [k_nope_h | k_rope]^T / sqrt(nope + rope), causal) v_h
    x = x + W_o [a_1 .. a_H]
    n = RMSNorm(x)
    dense layers:   x = x + SwiGLU(n)
    sparse layers:  s = sigmoid(W_r n); the k chosen are the top of
                    s + select_bias; w = scale . s / sum of the chosen s
                    x = x + sum over chosen, held e of w_e Expert_e(n)
                          + Shared(n)

Keys are wider than values (192 and 128 at the published sizes):
``ops.attention.causal_attention`` takes both widths.  The layer is told
which experts it holds (``n_held_experts`` from ``first_held_expert``, of
the ``n_routed_experts`` the router chooses among): it routes over all of
them and adds its own experts' part (``ops/moe.dropless_experts``;
DESIGN.md, held experts).  The vocabulary may likewise be this chip's rows.

Layout follows llama.py: block leaves stacked on a leading layer axis and
``lax.scan`` over them; two stacks, because a dense and a sparse layer's
leaves differ.  ``select_bias`` (``e_score_correction_bias``) decides the
choice only, has no gradient and is held fixed by the step (the rule that
moves it between steps is a training recipe, not part of the block).
The serving forwards (a latent cache, an absorbed decode) are not written.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models._common import (  # noqa: F401
    _rms_norm, _rope_interleaved, experts_in_place, next_token_nll,
    normal_init, remat_block, split_batch)

Params = Dict[str, Any]


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256         # the rows held here
    max_positions: int = 32768
    n_embd: int = 2048
    n_layer: int = 48                # dense + sparse
    n_dense_layer: int = 1           # first_k_dense_replace
    n_head: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_latent_dim: int = 512         # kv_lora_rank
    ffn_dim: int = 6144              # the dense layers' SwiGLU
    expert_dim: int = 768            # moe_intermediate_size
    n_routed_experts: int = 128      # the router's width
    n_held_experts: int = 128        # of them, held by this chip ...
    first_held_expert: int = 0       # ... from this one on
    experts_per_token: int = 6
    n_shared_experts: int = 2        # one SwiGLU of n_shared x expert_dim
    routed_scale: float = 2.448      # routed_scaling_factor
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"       # full | attn (llama.py)
    attn_impl: str = "auto"          # auto | dense | flash

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def n_sparse_layer(self) -> int:
        return self.n_layer - self.n_dense_layer


def tiny(vocab: int = 200, seq: int = 48, **changes) -> DeepseekV3Config:
    """The block at a test's size: one dense layer and two sparse ones, 8
    routed experts of which 4 are held, 2 a token, one shared."""
    return DeepseekV3Config(**{**dict(
        vocab_size=vocab, max_positions=seq, n_embd=64, n_layer=3,
        n_dense_layer=1, n_head=4, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=12, kv_latent_dim=16, ffn_dim=96, expert_dim=24,
        n_routed_experts=8, n_held_experts=4, first_held_expert=0,
        experts_per_token=2, n_shared_experts=1), **changes})


# ------------------------------------------------------------------- params
def init_params(rng: jax.Array, cfg: DeepseekV3Config) -> Params:
    """Every matrix normal at 0.02 (the family's ``initializer_range``),
    norm scales 1; ``select_bias`` normal at 0.01: small beside the
    scores' spread, and not zero, so that a choice it makes differs from
    the scores' own.  Each stacked leaf is drawn in one call."""
    pd = cfg.param_dtype
    E, H = cfg.n_embd, cfg.n_head
    k = iter(jax.random.split(rng, 24))

    def attention(L):
        def stacked(*shape):
            return normal_init(next(k), (L, *shape), pd)
        return {
            "attn_norm": {"scale": jnp.ones((L, E), pd)},
            "wq": {"kernel": stacked(E, H * cfg.qk_head_dim)},
            "wkv_a": {"kernel": stacked(E, cfg.kv_latent_dim
                                        + cfg.qk_rope_dim)},
            "kv_norm": {"scale": jnp.ones((L, cfg.kv_latent_dim), pd)},
            "wkv_b": {"kernel": stacked(
                cfg.kv_latent_dim, H * (cfg.qk_nope_dim + cfg.v_head_dim))},
            "wo": {"kernel": stacked(H * cfg.v_head_dim, E)},
            "mlp_norm": {"scale": jnp.ones((L, E), pd)},
        }

    def swiglu(L, width):
        return {"w_gate": {"kernel": normal_init(next(k), (L, E, width), pd)},
                "w_up": {"kernel": normal_init(next(k), (L, E, width), pd)},
                "w_down": {"kernel": normal_init(next(k), (L, width, E), pd)}}

    Ld, Ls = cfg.n_dense_layer, cfg.n_sparse_layer
    X, Xh, F = cfg.n_routed_experts, cfg.n_held_experts, cfg.expert_dim
    moe = attention(Ls)
    moe["router"] = {
        "kernel": normal_init(next(k), (Ls, E, X), pd),
        "select_bias": normal_init(next(k), (Ls, X), jnp.float32, 0.01)}
    moe["experts"] = {"w_gate": normal_init(next(k), (Ls, Xh, E, F), pd),
                      "w_up": normal_init(next(k), (Ls, Xh, E, F), pd),
                      "w_down": normal_init(next(k), (Ls, Xh, F, E), pd)}
    moe["shared"] = swiglu(Ls, cfg.n_shared_experts * F)
    return {
        "wte": normal_init(next(k), (cfg.vocab_size, E), pd),
        "dense_blocks": {**attention(Ld), **swiglu(Ld, cfg.ffn_dim)},
        "moe_blocks": moe,
        "norm_f": {"scale": jnp.ones((E,), pd)},
        "lm_head": {"kernel": normal_init(next(k), (E, cfg.vocab_size), pd)},
    }


# ------------------------------------------------------------------ forward
def _latent_attention(u: jax.Array, lp: Params, cfg: DeepseekV3Config):
    """Normed hidden states (B, T, E) -> W_o . attention (B, T, E).  Each
    of the kernel's operands is a projection's own result: W_q and W_kv_b
    are read by column group (a slice of the weight, not of 16,384 rows of
    activations), so no (nope + rope)-wide query or key and no joined
    gradient of one is ever built."""
    B, T, E = u.shape
    H, nope, rope = cfg.n_head, cfg.qk_nope_dim, cfg.qk_rope_dim
    latent = cfg.kv_latent_dim
    with jax.named_scope("attn_qkv"):
        wq = lp["wq"]["kernel"].astype(cfg.dtype).reshape(E, H, nope + rope)
        q_nope = jnp.einsum("bte,ehd->bthd", u, wq[..., :nope])
        q_rope = jnp.einsum("bte,ehd->bthd", u, wq[..., nope:])
    with jax.named_scope("mla/latent"):
        kva = u @ lp["wkv_a"]["kernel"].astype(cfg.dtype)
        c = _rms_norm(kva[..., :latent], lp["kv_norm"]["scale"], cfg.rms_eps)
        wkv_b = lp["wkv_b"]["kernel"].astype(cfg.dtype).reshape(
            latent, H, nope + cfg.v_head_dim)
        k_nope = jnp.einsum("btc,chd->bthd", c, wkv_b[..., :nope])
        v = jnp.einsum("btc,chd->bthd", c, wkv_b[..., nope:])
    with jax.named_scope("mla/rope"):
        q_rope = _rope_interleaved(q_rope, cfg.rope_theta)
        k_rope = _rope_interleaved(kva[..., None, latent:],
                                   cfg.rope_theta)[:, :, 0]
    with jax.named_scope("attn"):
        # the one rotary key a token goes once, for every head to read
        from ray_tpu.ops.attention import latent_causal_attention
        a = latent_causal_attention(q_nope, q_rope, k_nope, k_rope, v,
                                    impl=cfg.attn_impl)
    with jax.named_scope("attn_out"):
        return a.reshape(B, T, H * cfg.v_head_dim) \
            @ lp["wo"]["kernel"].astype(cfg.dtype)


def _swiglu(h: jax.Array, lp: Params, cfg: DeepseekV3Config) -> jax.Array:
    gate = jax.nn.silu(h @ lp["w_gate"]["kernel"].astype(cfg.dtype))
    up = h @ lp["w_up"]["kernel"].astype(cfg.dtype)
    return (gate * up) @ lp["w_down"]["kernel"].astype(cfg.dtype)


def _experts(h: jax.Array, lp: Params, cfg: DeepseekV3Config,
             stack: Optional[tuple] = None):
    """Normed hidden states (B, T, E) -> (the held routed experts' part
    plus the shared expert, HeldStats).  ``stack``:
    (``_common.experts_in_place`` of the sparse stack, this layer's index
    in it, traced), beside ``lp``'s own slice."""
    from ray_tpu.ops.moe import dropless_moe_ffn
    with jax.named_scope("moe"):
        ex, router = lp["experts"], lp["router"]
        routed, stats = dropless_moe_ffn(
            h.reshape(-1, h.shape[-1]), router["kernel"], ex["w_gate"],
            ex["w_up"], ex["w_down"], k=cfg.experts_per_token,
            scoring="sigmoid", select_bias=router["select_bias"],
            weight_scale=cfg.routed_scale, first_held=cfg.first_held_expert,
            stack=stack)
        with jax.named_scope("shared"):
            shared = _swiglu(h, lp["shared"], cfg)
    return routed.reshape(h.shape) + shared, stats


def _block(x: jax.Array, lp: Params, cfg: DeepseekV3Config, sparse: bool,
           stack: Optional[tuple] = None):
    """One decoder block -> (out, HeldStats | None).  The layer kinds
    shared with the other decoders run under GPT-2's scope names (ln_1,
    attn_qkv, attn_out, ln_2, mlp; models/gpt2.py)."""
    with jax.named_scope("ln_1"):
        u = _rms_norm(x, lp["attn_norm"]["scale"], cfg.rms_eps)
    x = x + _latent_attention(u, lp, cfg)
    with jax.named_scope("ln_2"):
        h = _rms_norm(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
    if sparse:
        f, stats = _experts(h, lp, cfg, stack)
        return x + f, stats
    with jax.named_scope("mlp"):
        return x + _swiglu(h, lp, cfg), None


def forward_hidden(params: Params, tokens: jax.Array, cfg: DeepseekV3Config):
    """tokens (B, T) int32 -> (final-norm hidden states (B, T, E) in
    cfg.dtype, the sparse layers' HeldStats stacked on a leading axis)."""
    with jax.named_scope("embed"):
        x = params["wte"].astype(cfg.dtype)[tokens]
    stats = None
    for name, sparse in (("dense_blocks", False), ("moe_blocks", True)):
        blocks = params[name]
        block = partial(_block, cfg=cfg, sparse=sparse)
        if sparse:
            # the kernels read a layer's experts in the stack, in place
            whole, blocks = (experts_in_place(blocks["experts"]),
                             (blocks, jnp.arange(cfg.n_sparse_layer)))

            def block(x, xs):
                return _block(x, xs[0], cfg, True, (whole, xs[1]))
        if cfg.remat:
            from ray_tpu.ops.attention import flash_runs
            block = remat_block(block, cfg.remat_policy,
                                flash_runs(tokens.shape[1], cfg.attn_impl))
        x, stats = lax.scan(block, x, blocks)
    with jax.named_scope("ln_f"):
        return _rms_norm(x, params["norm_f"]["scale"], cfg.rms_eps), stats


def forward(params: Params, tokens: jax.Array,
            cfg: DeepseekV3Config) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, vocab held) f32."""
    x, _ = forward_hidden(params, tokens, cfg)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
        return logits.astype(jnp.float32)


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: DeepseekV3Config) -> jax.Array:
    """Mean next-token cross entropy over the vocabulary rows held, a
    scalar; no auxiliary term (the bias balances).  Hands what the held
    experts saw to the train step's metrics (spmd.report_step_metrics)."""
    inp, tgt = split_batch(batch)
    x, stats = forward_hidden(params, inp, cfg)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
    from ray_tpu.parallel.spmd import report_step_metrics
    report_step_metrics(
        moe_held_rows=stats.held_rows.mean(),
        moe_held_load_max_over_mean=stats.load_max_over_mean.max(),
        moe_choice_share_held=stats.choice_share_held.mean(),
        moe_tile_fill=stats.tile_fill.mean())
    return next_token_nll(logits, tgt)

