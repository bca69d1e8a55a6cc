"""MoE decoder transformer — the expert-parallelism flagship.

No reference counterpart: Ray reaches MoE only through DeepSpeed inside
Train workers (SURVEY.md §2.4 "Expert parallelism — absent in core").  This
model pairs the GPT-2 attention stack with ``ray_tpu.ops.moe`` expert FFNs:
every layer's FFN is a top-k-routed expert bank whose weights carry a
leading ``num_experts`` axis sharded over the ``expert`` mesh axis — GSPMD
lowers token dispatch to all-to-alls over ICI.

Layer layout mirrors gpt2.py (stacked params + ``lax.scan``) so pipeline
parallelism (``pipeline_axis``) composes the same way; the aux losses ride
the scan as accumulated carries.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import gpt2 as gpt2_lib
from ray_tpu.models._common import (  # noqa: F401
    normal_init, param_count, split_batch)
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops import moe as moe_lib

Params = Dict[str, Any]


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    num_experts: int = 8
    expert_ff: int = 3072
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def moe_small() -> MoEConfig:  # ~8x124M-FFN experts
    return MoEConfig()


def tiny(vocab: int = 128, seq: int = 64, experts: int = 4) -> MoEConfig:
    return MoEConfig(vocab_size=vocab, n_positions=seq, n_embd=64, n_layer=2,
                     n_head=4, num_experts=experts, expert_ff=128)


PRESETS = {"moe-small": moe_small, "tiny": tiny}


# ------------------------------------------------------------------- params
def init_params(rng: jax.Array, cfg: MoEConfig) -> Params:
    pd = cfg.param_dtype
    E, H, L = cfg.n_embd, cfg.n_head, cfg.n_layer
    k = iter(jax.random.split(rng, 8 + 6 * L))

    def stack(f):
        return jnp.stack([f(next(k)) for _ in range(L)])

    def dense(kk, shape, scale=0.02):
        return normal_init(kk, shape, pd, scale)

    blocks = {
        "ln_1": {"scale": jnp.ones((L, E), pd), "bias": jnp.zeros((L, E), pd)},
        "attn_qkv": {"kernel": stack(lambda kk: dense(kk, (E, 3, E))),
                     "bias": jnp.zeros((L, 3, E), pd)},
        "attn_out": {"kernel": stack(lambda kk: dense(
            kk, (E, E), 0.02 / math.sqrt(2 * L))),
            "bias": jnp.zeros((L, E), pd)},
        "ln_2": {"scale": jnp.ones((L, E), pd), "bias": jnp.zeros((L, E), pd)},
        "moe": {
            "router": stack(lambda kk: dense(kk, (E, cfg.num_experts))),
            "w_in": stack(lambda kk: dense(
                kk, (cfg.num_experts, E, cfg.expert_ff),
                1.0 / math.sqrt(E))),
            "w_out": stack(lambda kk: dense(
                kk, (cfg.num_experts, cfg.expert_ff, E),
                1.0 / math.sqrt(cfg.expert_ff))),
        },
    }
    return {
        "wte": dense(next(k), (cfg.vocab_size, E)),
        "wpe": dense(next(k), (cfg.n_positions, E), 0.01),
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((E,), pd), "bias": jnp.zeros((E,), pd)},
    }


# ------------------------------------------------------------------ forward
def _block(x, lp, cfg: MoEConfig):
    """Attention (dense causal) + MoE FFN. Returns (y, (aux, z, dropped))."""
    B, T, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    h = gpt2_lib._layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"])
    qkv = jnp.einsum("bte,eck->btck", h,
                     lp["attn_qkv"]["kernel"].astype(cfg.dtype))
    qkv = qkv + lp["attn_qkv"]["bias"].astype(cfg.dtype)
    q, kk, v = [qkv[:, :, i, :].reshape(B, T, H, D) for i in range(3)]
    # dense: under GSPMD over the expert axis a Mosaic kernel cannot be
    # partitioned (ROADMAP D15)
    a = causal_attention(q, kk, v, impl="dense").reshape(B, T, E)
    a = a @ lp["attn_out"]["kernel"].astype(cfg.dtype) \
        + lp["attn_out"]["bias"].astype(cfg.dtype)
    x = x + a
    h = gpt2_lib._layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"])
    y, metrics = moe_lib.moe_ffn(
        h, lp["moe"]["router"].astype(jnp.float32),
        lp["moe"]["w_in"].astype(cfg.dtype),
        lp["moe"]["w_out"].astype(cfg.dtype),
        k=cfg.top_k, capacity_factor=cfg.capacity_factor)
    return x + y, (metrics.aux_loss, metrics.router_z_loss,
                   metrics.fraction_dropped)


def forward(params: Params, tokens: jax.Array, cfg: MoEConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (B, T) → (logits (B, T, vocab) f32, moe metrics)."""
    B, T = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    x = x + params["wpe"].astype(cfg.dtype)[jnp.arange(T)]

    block = partial(_block, cfg=cfg)
    if cfg.remat:
        block = jax.checkpoint(block)

    def body(carry, lp):
        y, m = block(carry, lp)
        return y, m

    x, (aux, z, dropped) = lax.scan(body, x, params["blocks"])
    x = gpt2_lib._layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = jnp.einsum("bte,ve->btv", x, params["wte"].astype(cfg.dtype))
    metrics = {"moe_aux_loss": aux.mean(), "moe_z_loss": z.mean(),
               "moe_fraction_dropped": dropped.mean()}
    return logits.astype(jnp.float32), metrics


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: MoEConfig) -> jax.Array:
    inp, tgt = split_batch(batch)
    logits, metrics = forward(params, inp, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return (nll.mean()
            + cfg.aux_loss_weight * metrics["moe_aux_loss"]
            + cfg.z_loss_weight * metrics["moe_z_loss"])


# Sharding rules: MoE rules first (most specific), then the transformer set.
from ray_tpu.parallel.mesh import TRANSFORMER_RULES as _TR  # noqa: E402

MOE_TRANSFORMER_RULES = moe_lib.MOE_RULES + _TR
