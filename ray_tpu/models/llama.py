"""Llama-family decoder (RMSNorm + RoPE + GQA + SwiGLU), TPU-first.

No reference counterpart (Ray ships no models; SURVEY.md §2.5) — included
so the framework's flagship set covers the modern decoder recipe alongside
GPT-2.  Same architecture conventions as the public Llama-2/3 papers:
pre-RMSNorm, rotary position embeddings, grouped-query attention, SwiGLU
MLP, untied output head.  Layout follows gpt2.py: stacked per-layer params
+ ``lax.scan`` (pipeline-axis ready), bf16 activations / f32 params,
attention through ``ops.attention.causal_attention``, which chooses.

Three stacks live here, told apart by the configuration alone: the dense
Llama block; OLMoE's (``qk_norm`` over the whole projection, ``n_experts``
dropless experts weighed by their probabilities as they are); and the
Qwen3-MoE stack that SDAR-30B-A3B generates with by diffusion over blocks
(``head_size``: heads of their own size, ``H x D != E``; ``qk_norm_heads``:
the norm a head; ``norm_topk``; ``block_length`` / ``mask_token_id``: the
block-causal mask in training and prefill, and ``block_stepping``, which
tells the serving stack to step a sequence a block of positions at a time:
``_forward_decode_blocks``).  The serving forwards keep the experts outside
the layer scan's slices (``_split_experts``).

A learned index (``index_topk`` > 0; Keye-VL-2.0-30B-A3B's language model,
DeepSeek Sparse Attention's lightning indexer on the Qwen3-MoE stack).
Every layer holds an indexer beside its attention: ``index_heads`` query
heads of ``index_dim`` lanes, ONE key head (LayerNorm, then RoPE over all
its lanes) and a weight a head, all projected from the attention's own
input; a query attends to the ``index_topk`` positions of largest ``I_t,s =
sum_j w_t,j ReLU(qI_t,j . kI_s)``, ``s <= t`` (``ops/indexed_attention.py``:
exact, a position's own).  Such a preset is served alone: a prompt runs in
chunks of ``prefill_chunk`` positions over a staging that carries the index
keys beside K/V (:func:`forward_prefill_chunk`, :func:`prefill_staging`;
:func:`forward_prefill` is the same code over a whole prompt as one run),
the decode step is handed the index plane (``index_pool``), and a
position's index key leaves every serving forward as ONE MORE HEAD OF ITS
K (its first ``index_dim`` lanes; ``kv_cache.py``, the ``"index"`` row, says
why).  With ``index_topk`` 0 none of this is in a program or a tree.

A folded cache (``eva_window`` > 0; EvaByte, Llama-2-7B's stack to the last
width under EVA's attention, ``ops/eva_attention.py``).  A query sees its own
window of ``eva_window`` positions exactly and every window that has closed
as one folded key and value for every ``eva_chunk`` positions, through one
softmax.  Such a preset is served alone too: a prompt runs in chunks of one
window over a staging laid out as the rows a sequence HOLDS (``[a closed
window's folded rows]* + [the open window's rows]``), each chunk causal in
those coordinates and folded at its end (:func:`_run_eva`); a decode step is
handed the rows a sequence holds as its ``ctx_lens`` and the positions it has
seen as ``positions``, and reads the shrunk table through the plain paged
walk; a window that closes in decode is folded out of the pool by
:func:`fold_window`.  The stream stays float32 between layers
(``residual_f32``), the norms multiply by ``1 + g`` (``norm_offset``) and the
head is ``pred_heads`` heads of ``vocab_size`` side by side, head 0 the next
token: the serving forwards return head 0's logits, :func:`forward` all of
them.  With ``eva_window`` 0 none of this is in a program or a tree.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.models._common import (  # noqa: F401
    _gqa_expand, _rms_norm, _rope, _rope_at, experts_in_place,
    next_token_nll, normal_init, param_count, remat_block, split_batch)

Params = Dict[str, Any]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_positions: int = 4096
    n_embd: int = 4096
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32          # < n_head → grouped-query attention
    ffn_dim: int = 11008         # SwiGLU hidden
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # what init_params stores and training updates; a serving program is
    # handed _common.serving_params of the tree (see WIDE_PARAMS)
    param_dtype: Any = jnp.float32
    remat: bool = True
    # _common.remat_block: full recomputes the block in the backward;
    # attn keeps the flash kernel's output and lse, so that the backward
    # does not run the forward kernel again; needs flash
    remat_policy: str = "full"   # full | attn | attn_qkv
    # passed to ops.attention.causal_attention, which chooses: "auto" is
    # the Pallas flash kernel where the code can see a TPU and the
    # kernel's block tiles the sequence, XLA dense elsewhere
    attn_impl: str = "auto"      # auto | dense | flash | ring | ulysses
    context_axis: Optional[str] = None
    # RMSNorm over the whole projected q and k (own scales), before the
    # split into heads and before RoPE (OLMoE); with ``qk_norm_heads`` a
    # head at a time over its head_dim lanes, one scale for all heads
    # (Qwen3)
    qk_norm: bool = False
    qk_norm_heads: bool = False
    # a head's size where it is not n_embd / n_head (0: it is): q and wo
    # are then n_head * head_size wide beside n_embd
    head_size: int = 0
    # > 0: the FFN is n_experts SwiGLU experts of width ffn_dim,
    # experts_per_token of them a token, dropless (ops/moe.py); the loss
    # gains the two router terms at these coefficients
    n_experts: int = 0
    experts_per_token: int = 0
    router_aux_coef: float = 0.0
    router_z_coef: float = 0.0
    # the chosen experts' weights over their own sum (ops/moe.route_softmax)
    norm_topk: bool = False
    # False: wo and w_down start at 0.02 like every other matrix
    scaled_residual_init: bool = True
    # every matrix normal at 1 / sqrt(fan_in) (the experts' w_down sqrt(k)
    # more), so that random weights give logits of unit spread: a served
    # configuration whose check compares logits (perfbench/SDAR.md)
    fan_in_init: bool = False
    # > 1: generation by diffusion over blocks of this many positions.  The
    # mask is block-causal (a position sees every earlier block whole and
    # its own in both directions, blocks counted from position 0), an
    # undecided position is fed ``mask_token_id``, and the serving stack
    # steps a sequence a block at a time (``block_stepping``).  0: a model
    # that steps by tokens under the causal mask
    block_length: int = 0
    mask_token_id: Optional[int] = None
    # denoise passes a block (0: block_length): each fixes the
    # block_length / denoising_steps undecided positions of highest
    # confidence
    denoising_steps: int = 0
    # > 0: every layer holds an indexer (``index_heads`` x ``index_dim``
    # queries, one key head, a weight a head) and a query attends to the
    # ``index_topk`` positions it scores highest; 0: none, and nothing of
    # it is in the tree or in a program
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # positions one prefill program runs where a prompt runs in chunks (a
    # preset with an index); 0: a prompt is one program of its bucket
    prefill_chunk: int = 0
    # > 0: EVA's folded attention.  A query sees the positions of its own
    # window of ``eva_window`` exactly and every closed window as one
    # folded key and value for every ``eva_chunk`` positions (``phi``,
    # ``mu`` a head a layer: ``blocks["eva"]``); a prompt runs in chunks of
    # one window.  0: none, and nothing of it is in the tree or a program
    eva_window: int = 0
    eva_chunk: int = 0
    # heads of ``vocab_size`` logits side by side in ``lm_head`` (head i
    # scores the token at t + 1 + i); the serving forwards return head 0
    pred_heads: int = 1
    # RMSNorm multiplies by ``1 + g`` (the scales start at 0)
    norm_offset: bool = False
    # the stream between the layers is float32: every residual add
    residual_f32: bool = False

    def __post_init__(self):
        if self.eva_window and (
                self.eva_chunk < 1 or self.eva_window % self.eva_chunk
                or self.prefill_chunk != self.eva_window or self.n_experts
                or self.index_topk or self.block_length > 1):
            raise ValueError(
                "a folded cache needs a window of whole chunks, a "
                "prefill_chunk of one window, and a dense model without an "
                "index that steps by tokens")
        if self.index_topk and (
                self.block_length > 1 or not 0 < self.index_dim
                <= self.head_dim or self.index_heads < 1
                or self.prefill_chunk < 1):
            raise ValueError(
                "an index needs heads, a key of 1..head_dim lanes (it "
                "leaves the forwards as one more head of K), a "
                "prefill_chunk, and a model that steps by tokens")

    @property
    def head_dim(self) -> int:
        return self.head_size or self.n_embd // self.n_head


def llama2_7b() -> LlamaConfig:
    return LlamaConfig()


def llama3_8b() -> LlamaConfig:
    return LlamaConfig(vocab_size=128256, n_embd=4096, n_layer=32,
                       n_head=32, n_kv_head=8, ffn_dim=14336,
                       rope_theta=500000.0, max_positions=8192)


def tiny(vocab: int = 128, seq: int = 64) -> LlamaConfig:
    return LlamaConfig(vocab_size=vocab, max_positions=seq, n_embd=64,
                       n_layer=2, n_head=4, n_kv_head=2, ffn_dim=128)


def tiny_moe(vocab: int = 200, seq: int = 48) -> LlamaConfig:
    """OLMoE's block at a test's size: QK-norm, 8 experts, 2 a token."""
    return LlamaConfig(vocab_size=vocab, max_positions=seq, n_embd=64,
                       n_layer=2, n_head=4, n_kv_head=4, ffn_dim=32,
                       qk_norm=True, n_experts=8, experts_per_token=2,
                       router_aux_coef=0.01, router_z_coef=0.001,
                       scaled_residual_init=False)


def tiny_sdar(vocab: int = 200, seq: int = 96) -> LlamaConfig:
    """SDAR's (Qwen3-MoE's) block at a test's size: heads of 16 beside a
    hidden size of 32 (H x D != E), a QK-norm a head, 8 experts, 2 a token
    with their weights renormalised, blocks of 4 positions."""
    return LlamaConfig(vocab_size=vocab, max_positions=seq, n_embd=32,
                       n_layer=2, n_head=4, n_kv_head=2, head_size=16,
                       ffn_dim=16, rope_theta=1e6, rms_eps=1e-6,
                       qk_norm=True, qk_norm_heads=True, n_experts=8,
                       experts_per_token=2, norm_topk=True,
                       scaled_residual_init=False, fan_in_init=True,
                       block_length=4, mask_token_id=vocab - 1,
                       denoising_steps=4)


def sdar_30b_a3b_l6() -> LlamaConfig:
    """SDAR-30B-A3B-Chat's widths with 6 of its 48 layers (one of eight
    pipeline stages; perfbench/configs/sdar-30b-a3b-chat.json), served in
    bf16 (``param_dtype``: the tree is drawn in its serving type)."""
    return LlamaConfig(vocab_size=151936, max_positions=32768, n_embd=2048,
                       n_layer=6, n_head=32, n_kv_head=4, head_size=128,
                       ffn_dim=768, rope_theta=1e6, rms_eps=1e-6,
                       qk_norm=True, qk_norm_heads=True, n_experts=128,
                       experts_per_token=8, norm_topk=True,
                       scaled_residual_init=False, fan_in_init=True,
                       param_dtype=jnp.bfloat16, remat=False,
                       block_length=4, mask_token_id=151669,
                       denoising_steps=4)


def tiny_keye(vocab: int = 200, seq: int = 128) -> LlamaConfig:
    """Keye-VL-2.0's block at a test's size: ``tiny_sdar``'s stack stepped
    by tokens, an indexer of 4 heads x 8 in every layer, 12 positions
    chosen (far under its contexts), chunks of 32."""
    return LlamaConfig(vocab_size=vocab, max_positions=seq, n_embd=32,
                       n_layer=2, n_head=4, n_kv_head=2, head_size=16,
                       ffn_dim=16, rope_theta=1e7, rms_eps=1e-6,
                       qk_norm=True, qk_norm_heads=True, n_experts=8,
                       experts_per_token=2, norm_topk=True,
                       scaled_residual_init=False, fan_in_init=True,
                       index_heads=4, index_dim=8, index_topk=12,
                       prefill_chunk=32, dtype=jnp.float32)


def keye_vl_2_30b_a3b_l6() -> LlamaConfig:
    """Keye-VL-2.0-30B-A3B's language model at its published widths with 6
    of its 48 layers (one of eight pipeline stages;
    perfbench/configs/keye-vl-2.0-30b-a3b.json), served in bf16."""
    return LlamaConfig(vocab_size=151936, max_positions=262144, n_embd=2048,
                       n_layer=6, n_head=32, n_kv_head=4, head_size=128,
                       ffn_dim=768, rope_theta=1e7, rms_eps=1e-6,
                       qk_norm=True, qk_norm_heads=True, n_experts=128,
                       experts_per_token=8, norm_topk=True,
                       scaled_residual_init=False, fan_in_init=True,
                       param_dtype=jnp.bfloat16, remat=False,
                       index_heads=16, index_dim=64, index_topk=2048,
                       prefill_chunk=2048)


def tiny_eva(vocab: int = 64, seq: int = 160) -> LlamaConfig:
    """EvaByte's block at a test's size: windows of 32 positions folded 4
    to 1, 4 heads of 16, 2 prediction heads, float32."""
    return LlamaConfig(vocab_size=vocab, max_positions=seq, n_embd=64,
                       n_layer=2, n_head=4, n_kv_head=4, ffn_dim=128,
                       rope_theta=1e5, scaled_residual_init=False,
                       fan_in_init=True, eva_window=32, eva_chunk=4,
                       prefill_chunk=32, pred_heads=2, norm_offset=True,
                       residual_f32=True, dtype=jnp.float32)


def evabyte_6_5b_l8() -> LlamaConfig:
    """EvaByte's published widths with 8 of its 32 layers (one of four
    pipeline stages; perfbench/configs/evabyte-6.5b.json), served in
    bf16."""
    return LlamaConfig(vocab_size=320, max_positions=32768, n_embd=4096,
                       n_layer=8, n_head=32, n_kv_head=32, ffn_dim=11008,
                       rope_theta=1e5, rms_eps=1e-5,
                       scaled_residual_init=False, fan_in_init=True,
                       param_dtype=jnp.bfloat16, remat=False,
                       eva_window=2048, eva_chunk=16, prefill_chunk=2048,
                       pred_heads=8, norm_offset=True, residual_f32=True)


PRESETS = {"llama2-7b": llama2_7b, "llama3-8b": llama3_8b, "tiny": tiny,
           "tiny-moe": tiny_moe, "tiny-sdar": tiny_sdar,
           "sdar-30b-a3b-l6": sdar_30b_a3b_l6, "tiny-keye": tiny_keye,
           "keye-vl-2.0-30b-a3b-l6": keye_vl_2_30b_a3b_l6,
           "tiny-eva": tiny_eva, "evabyte-6.5b-l8": evabyte_6_5b_l8}


# ------------------------------------------------------------------- params
def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Block leaves are stacked on a leading n_layer axis (and the experts'
    on an n_experts axis behind it), each drawn in one call."""
    pd = cfg.param_dtype
    E, L, F = cfg.n_embd, cfg.n_layer, cfg.ffn_dim
    q_dim = cfg.n_head * cfg.head_dim        # E, but for a head_size
    kv_dim = cfg.n_kv_head * cfg.head_dim
    k = iter(jax.random.split(rng, 10))
    out_scale = 0.02 / math.sqrt(2 * L) if cfg.scaled_residual_init else 0.02
    fan_in = cfg.fan_in_init

    def stacked(*shape, scale=0.02, gain=1.0):
        if fan_in:
            scale = gain / math.sqrt(shape[-2])
        return normal_init(next(k), (L, *shape), pd, scale)

    # under norm_offset a norm multiplies by 1 + g: g starts at 0
    ones = jnp.zeros if cfg.norm_offset else jnp.ones
    blocks = {
        "attn_norm": {"scale": ones((L, E), pd)},
        "wq": {"kernel": stacked(E, q_dim)},
        "wk": {"kernel": stacked(E, kv_dim)},
        "wv": {"kernel": stacked(E, kv_dim)},
        "wo": {"kernel": stacked(q_dim, E, scale=out_scale)},
        "mlp_norm": {"scale": ones((L, E), pd)},
    }
    if cfg.qk_norm:
        per = cfg.head_dim if cfg.qk_norm_heads else 0
        blocks["q_norm"] = {"scale": jnp.ones((L, per or q_dim), pd)}
        blocks["k_norm"] = {"scale": jnp.ones((L, per or kv_dim), pd)}
    if cfg.n_experts:
        X = cfg.n_experts
        blocks["router"] = {"kernel": stacked(E, X)}
        # under fan_in_init sqrt(k) more: k experts weighed about 1 / k
        # each then add what one ffn adds
        blocks["experts"] = {"w_gate": stacked(X, E, F),
                             "w_up": stacked(X, E, F),
                             "w_down": stacked(
                                 X, F, E, scale=out_scale,
                                 gain=math.sqrt(cfg.experts_per_token))}
    else:
        blocks["w_gate"] = {"kernel": stacked(E, F)}
        blocks["w_up"] = {"kernel": stacked(E, F)}
        blocks["w_down"] = {"kernel": stacked(F, E, scale=out_scale)}
    if cfg.index_topk:
        # keys of its own, so that every other leaf is drawn as it was
        rest, k = k, iter(jax.random.split(jax.random.fold_in(rng, 1), 3))
        IH, ID = cfg.index_heads, cfg.index_dim
        blocks["index"] = {
            "wq": {"kernel": stacked(E, IH * ID)},
            "wk": {"kernel": stacked(E, ID)},
            "ww": {"kernel": stacked(E, IH)},
            "index_norm": {"scale": jnp.ones((L, ID), pd),
                           "bias": jnp.zeros((L, ID), pd)}}
        k = rest
    if cfg.eva_window:
        # keys of its own; normal, clipped to +-1, at 1 / sqrt(head_dim)
        D = cfg.head_dim
        drawn = jax.random.normal(jax.random.fold_in(rng, 2),
                                  (2, L, cfg.n_kv_head, D), jnp.float32)
        phi, mu = (jnp.clip(drawn, -1.0, 1.0) / math.sqrt(D)).astype(pd)
        blocks["eva"] = {"phi": phi, "mu": mu}
    table = 1.0 / math.sqrt(E) if fan_in else 0.02
    return {
        "wte": normal_init(next(k), (cfg.vocab_size, E), pd, table),
        "blocks": blocks,
        "norm_f": {"scale": ones((E,), pd)},
        "lm_head": {"kernel": normal_init(
            next(k), (E, cfg.pred_heads * cfg.vocab_size), pd, table)},
    }


# The keys whose leaves the forwards use as stored: _rms_norm multiplies
# its scale in float32.  Every other leaf is cast at its use, by
# ``.astype(cfg.dtype)`` here or by ``w.astype(x.dtype)`` in
# ops/moe.dropless_moe_ffn (the router and the experts), so
# _common.serving_params may store it in cfg.dtype.
WIDE_PARAMS = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "norm_f",
               "index_norm")


# ------------------------------------------------------------------ forward
# The layer kinds run under GPT-2's ``jax.named_scope`` names (embed, ln_1,
# attn_qkv, attn_out, ln_2, mlp, ln_f, lm_head; models/gpt2.py) beside this
# block's own (qk_norm, rope, attn, and the router and moe_* of ops/moe.py).
# Metadata only: PERF.md section 3 lists the metric that reads each.
def _embed(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    with jax.named_scope("embed"):
        return params["wte"].astype(cfg.dtype)[tokens]


def _final_norm(params: Params, x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    with jax.named_scope("ln_f"):
        return _rms_norm(x, params["norm_f"]["scale"], cfg.rms_eps)


def _head(params: Params, x: jax.Array, cfg: LlamaConfig,
          only_position: bool = False) -> jax.Array:
    """Final-norm hidden states -> float32 logits; ``only_position``:
    x is (B, 1, E) and the logits (B, V)."""
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
        if only_position:
            logits = logits[:, 0]
        return logits.astype(jnp.float32)


def _qkv(h: jax.Array, lp: Params, cfg: LlamaConfig):
    """Normed hidden states (..., E) -> q (..., H, D), k, v (..., KV, D),
    before RoPE."""
    H, D, KV = cfg.n_head, cfg.head_dim, cfg.n_kv_head
    with jax.named_scope("attn_qkv"):
        q = h @ lp["wq"]["kernel"].astype(cfg.dtype)
        k = h @ lp["wk"]["kernel"].astype(cfg.dtype)
        v = h @ lp["wv"]["kernel"].astype(cfg.dtype)
    lead = h.shape[:-1]
    if cfg.qk_norm and cfg.qk_norm_heads:
        # a head at a time, over its D lanes, one scale for all heads
        with jax.named_scope("qk_norm"):
            q = _rms_norm(q.reshape(*lead, H, D), lp["q_norm"]["scale"],
                          cfg.rms_eps)
            k = _rms_norm(k.reshape(*lead, KV, D), lp["k_norm"]["scale"],
                          cfg.rms_eps)
        return q, k, v.reshape(*lead, KV, D)
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = _rms_norm(q, lp["q_norm"]["scale"], cfg.rms_eps)
            k = _rms_norm(k, lp["k_norm"]["scale"], cfg.rms_eps)
    return (q.reshape(*lead, H, D), k.reshape(*lead, KV, D),
            v.reshape(*lead, KV, D))


def _index_proj(x: jax.Array, lp: Params, cfg: LlamaConfig,
                positions: jax.Array):
    """The layer's indexer on the attention's own input: the stream x (T,
    E), its positions (T,) -> (qI (T, IH, ID) and kI (T, ID), rotated over
    all their lanes; w (T, IH)), all float32.  The key goes through a
    LayerNorm (scale and bias) before its rotation; the queries are neither
    normed nor scaled.

    In float32 whatever ``cfg.dtype`` is: the attention's input is normed
    once more without the rounding to ``cfg.dtype``, and the three
    projections run at full precision.  A score decides a position's
    membership and not a weight, so what rounding moves it by is positions
    exchanged at the cut, and an average over ``index_topk`` values moves
    with every exchange: with the projections in bf16 a first layer's
    attention, which at random weights is most of its stream, differed from
    the float32 reference's by a sixth of itself (perfbench/KEYE.md).  The
    cost is 1,104 columns a layer; the index plane is float32 already."""
    ix, IH, ID = lp["index"], cfg.index_heads, cfg.index_dim
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    with jax.named_scope("index_proj"):
        h = _rms_norm(x.astype(f32), lp["attn_norm"]["scale"], cfg.rms_eps)
        q = jnp.dot(h, ix["wq"]["kernel"].astype(f32),
                    precision=hi).reshape(-1, IH, ID)
        k = jnp.dot(h, ix["wk"]["kernel"].astype(f32), precision=hi)
        w = jnp.dot(h, ix["ww"]["kernel"].astype(f32), precision=hi)
        k = k - k.mean(-1, keepdims=True)
        k = k * lax.rsqrt((k * k).mean(-1, keepdims=True) + cfg.rms_eps)
        k = k * ix["index_norm"]["scale"] + ix["index_norm"]["bias"]
        q = _rope_at(q, positions, cfg.rope_theta)
        k = _rope_at(k[:, None], positions, cfg.rope_theta)[:, 0]
    return q, k, w


def _ffn(h: jax.Array, lp: Params, cfg: LlamaConfig,
         served: Optional[tuple] = None, live: Optional[jax.Array] = None,
         stack: Optional[tuple] = None):
    """The block's feed-forward on normed hidden states (..., E): one
    SwiGLU, or the dropless experts.  Returns (out, RouterStats | None);
    training, prefill and decode all come through here.  ``served`` (a
    serving forward of a model with experts): (the experts of EVERY layer
    as one run of groups, :func:`_split_experts`; this layer's index,
    traced), and the second result is then the chosen expert ids, (rows,
    k) int32, from the routing that made ``out``; ``live`` (rows,) bool: a
    decode step's rows that are some sequence's
    (``ops/moe.choice_of_live_rows``).  ``stack`` (a training forward):
    (``_common.experts_in_place`` of the stack, this layer's index,
    traced), beside ``lp``'s own slice."""
    if cfg.n_experts:
        from ray_tpu.ops.moe import dropless_moe_ffn
        ex, layer = served or (lp["experts"], None)
        out, *told = dropless_moe_ffn(
            h.reshape(-1, h.shape[-1]), lp["router"]["kernel"],
            ex["w_gate"], ex["w_up"], ex["w_down"], k=cfg.experts_per_token,
            norm_topk=cfg.norm_topk, choices=served is not None, live=live,
            stack_at=layer, stack=stack)
        return out.reshape(h.shape), told[-1]        # the stats, or the ids
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(h @ lp["w_gate"]["kernel"].astype(cfg.dtype))
        up = h @ lp["w_up"]["kernel"].astype(cfg.dtype)
        return (gate * up) @ lp["w_down"]["kernel"].astype(cfg.dtype), None


def _split_experts(blocks: Params, cfg: LlamaConfig):
    """The stacked block leaves -> (what a serving forward's layer scan
    slices a layer of: every leaf but the experts'; the experts' leaves
    whole, ``(layers x experts, ...)``, or None for a dense model).

    The grouped matmuls are kernels, and a kernel's operand is a buffer: a
    layer's experts sliced out of their stack by the scan are copied whole
    for each of the three (3 x 1.2 GB a layer at SDAR's widths, 22 of a
    40 ms pass: seen in the cell's first trace, PERF.md PR 64; LFM2 met the
    same in PR 39).  So in the serving forwards the experts stay outside the
    scan's slices: all layers' experts are ONE run of groups, a layer names
    its own as groups ``layer x X .. layer x X + X - 1`` and the other
    layers' groups are empty (``ops/moe.dropless_moe_ffn``'s ``stack_at``).
    Training reads the stack in place too, by a road that keeps a layer's
    gradient in the layer's shape: :func:`forward_hidden` scans the whole
    stack and hands ``_common.experts_in_place`` beside each slice."""
    if not cfg.n_experts:
        return blocks, None
    sliced = {k: v for k, v in blocks.items() if k != "experts"}
    # cast as stored (a no-op on a serving tree), then one run of groups
    whole = {k: w.astype(cfg.dtype).reshape(-1, *w.shape[2:])
             for k, w in blocks["experts"].items()}
    return sliced, whole


def _block(x: jax.Array, lp: Params, cfg: LlamaConfig,
           collect_kv: bool = False, choices: bool = False,
           served: Optional[tuple] = None, stack: Optional[tuple] = None):
    """One decoder block -> (out, RouterStats | None); with ``collect_kv``
    -> (out, (k, v)), post-RoPE and pre-GQA-expand: the SAME body serves
    training and the serving engine's prefill cache fill, so the paths
    cannot diverge.  ``choices`` (with ``collect_kv``): -> (out, (k, v,
    the chosen expert ids))."""
    B, T, E = x.shape
    H = cfg.n_head
    with jax.named_scope("ln_1"):
        h = _rms_norm(x, lp["attn_norm"]["scale"], cfg.rms_eps)
    q, k, v = _qkv(h, lp, cfg)
    with jax.named_scope("rope"):
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    ke, ve = _gqa_expand(k, H), _gqa_expand(v, H)
    with jax.named_scope("attn"):
        from ray_tpu.ops.attention import causal_attention
        # under a block length the mask is block-causal, in training and
        # in prefill alike
        masked = {"block": cfg.block_length} if cfg.block_length > 1 else {}
        a = causal_attention(q, ke, ve, impl=cfg.attn_impl,
                             context_axis=cfg.context_axis,
                             **masked).reshape(B, T, H * cfg.head_dim)
    with jax.named_scope("attn_out"):
        x = x + a @ lp["wo"]["kernel"].astype(cfg.dtype)
    with jax.named_scope("ln_2"):
        h = _rms_norm(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
    f, stats = _ffn(h, lp, cfg, served, stack=stack)
    out = x + f
    if collect_kv:
        return out, ((k, v, stats) if choices else (k, v))
    return out, stats


def forward_hidden(params: Params, tokens: jax.Array, cfg: LlamaConfig):
    """tokens (B, T) int32 -> (final-norm hidden states (B, T, E) in
    cfg.dtype, the layers' RouterStats stacked on a leading n_layer axis,
    or None for a dense model)."""
    if cfg.index_topk:
        raise NotImplementedError(
            "training under a learned index is not written: the indexer "
            "has no loss of its own here (ROADMAP, Reach)")
    if cfg.eva_window:
        raise NotImplementedError(
            "training under a folded cache is not written: the fold has "
            "no backward here (ROADMAP, Reach)")
    x = _embed(params, tokens, cfg)
    blocks = params["blocks"]
    block = partial(_block, cfg=cfg)
    if cfg.n_experts:
        # the kernels read a layer's experts in the stack, in place
        whole, blocks = (experts_in_place(blocks["experts"]),
                         (blocks, jnp.arange(cfg.n_layer)))

        def block(x, xs):
            return _block(x, xs[0], cfg, stack=(whole, xs[1]))
    if cfg.remat:
        from ray_tpu.ops.attention import flash_runs
        block = remat_block(block, cfg.remat_policy,
                            flash_runs(tokens.shape[1], cfg.attn_impl))

    x, stats = lax.scan(block, x, blocks)
    return _final_norm(params, x, cfg), stats


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """tokens (B, T) int32 → logits (B, T, vocab) f32; under
    ``pred_heads`` > 1 every head's, (B, T, pred_heads, vocab)."""
    if cfg.eva_window:
        return _forward_eva(params, tokens, cfg, None, heads=True)[0]
    if cfg.index_topk:
        return forward_prefill(params, tokens, cfg)[0]
    x, _ = forward_hidden(params, tokens, cfg)
    return _head(params, x, cfg)


# -------------------------------------------------- inference (KV cache)
def block_stepping(cfg: LlamaConfig) -> Optional[Dict[str, Any]]:
    """How the serving stack steps a sequence of a preset that generates by
    diffusion over blocks (``serve/llm/model_runner.py``, ``engine.py``):
    ``block`` positions a decode pass, undecided ones fed ``mask_id``;
    ``per_pass`` positions fixed a pass, those of highest confidence.
    None: the preset steps by tokens."""
    if cfg.block_length <= 1:
        return None
    if cfg.mask_token_id is None or not 0 <= cfg.mask_token_id < \
            cfg.vocab_size:
        raise ValueError(f"blocks of {cfg.block_length} positions need a "
                         f"mask_token_id inside the {cfg.vocab_size} rows")
    steps = cfg.denoising_steps or cfg.block_length
    if cfg.block_length % steps:
        raise ValueError(f"{steps} denoising steps do not divide a block "
                         f"of {cfg.block_length}")
    return {"block": cfg.block_length, "mask_id": cfg.mask_token_id,
            "per_pass": cfg.block_length // steps}


def routed_layers(cfg: LlamaConfig) -> Optional[Dict[str, int]]:
    """What the serving step programs of a preset with experts hand over
    beside the logits (``serve/llm/model_runner.py``): the expert ids each
    layer chose, int32 (layers, rows, k).  None: the preset is dense."""
    if not cfg.n_experts:
        return None
    return {"layers": cfg.n_layer, "k": cfg.experts_per_token}


def cache_layers(cfg: LlamaConfig) -> Dict[str, int]:
    """Every layer holds K/V and, under an index, one index key a position
    beside it (``serve/llm/kv_cache.py``, the ``"index"`` row)."""
    kinds = {"kv": cfg.n_layer, "state": 0}
    if cfg.index_topk:
        kinds["index"] = cfg.n_layer
    return kinds


def folded_cache(cfg: LlamaConfig) -> Optional[Dict[str, int]]:
    """What ``kv_cache.kept_by`` is told of a sequence that folds: a closed
    ``window`` of positions is kept as one row a ``chunk``.  None: a
    sequence keeps every position's row."""
    if not cfg.eva_window:
        return None
    return {"window": cfg.eva_window, "chunk": cfg.eva_chunk}


def prefill_staging(cfg: LlamaConfig,
                    positions: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """What a prompt's chunks keep between them (a preset with an index):
    every layer's K and V, a position a row of ``KV x D`` lanes, and its
    index key in the first ``index_dim`` lanes of a row a head wide, the
    form in which it rides behind K's heads; ``positions`` whole chunks.

    Under a folded cache: K and V alone, laid out as the rows a sequence
    HOLDS, the folded rows of the windows before the last and the last
    window's own (whole key tiles where there are several)."""
    if cfg.eva_window:
        per = cfg.eva_window // cfg.eva_chunk
        rows = (positions // cfg.eva_window - 1) * per + cfg.eva_window
        rows += -rows % (512 if rows > 512 else per)
        kv = jax.ShapeDtypeStruct(
            (cfg.n_layer, rows, cfg.n_kv_head * cfg.head_dim), jnp.float32)
        return {"k": kv, "v": kv}
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layer, positions, cfg.n_kv_head * cfg.head_dim), jnp.float32)
    return {"k": kv, "v": kv, "index": jax.ShapeDtypeStruct(
        (cfg.n_layer, positions, -(-cfg.head_dim // 128) * 128), jnp.float32)}


def _run_indexed(params: Params, tokens: jax.Array, cfg: LlamaConfig, start,
                 staging: Dict[str, jax.Array], choices: bool):
    """A run of one prompt's positions ``start .. start + T - 1`` through
    every layer of a preset with an index: tokens (T,), those past the
    prompt's end padding (a query admits nothing past its own position, so
    no real query sees them and the next prompt overwrites what they
    stage); ``staging`` as :func:`prefill_staging` says, holding every
    earlier position.  Returns (the stream (T, E), the staging with
    this run's positions, the chosen expert ids (L, T, k) or None)."""
    from ray_tpu.ops import indexed_attention as indexed
    T = tokens.shape[0]
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    positions = start + jnp.arange(T, dtype=jnp.int32)
    x = _embed(params, tokens, cfg)
    sliced, experts = _split_experts(params["blocks"], cfg)
    f32 = jnp.float32

    def body(carry, xs):
        x, staging = carry
        lp, layer = xs
        with jax.named_scope("ln_1"):
            h = _rms_norm(x, lp["attn_norm"]["scale"], cfg.rms_eps)
        q, k, v = _qkv(h, lp, cfg)
        with jax.named_scope("rope"):
            q = _rope_at(q, positions, cfg.rope_theta)
            k = _rope_at(k, positions, cfg.rope_theta)
        qi, ki, w = _index_proj(x, lp, cfg, positions)
        with jax.named_scope("kv_stage"):
            lanes = staging["index"].shape[-1]
            rows = {"k": k.reshape(T, KV * D), "v": v.reshape(T, KV * D),
                    "index": jnp.pad(ki, ((0, 0),
                                          (0, lanes - cfg.index_dim)))}
            staging = {name: lax.dynamic_update_slice(
                staging[name], rows[name].astype(f32)[None],
                (layer, start, 0)) for name in staging}
            staged = {name: lax.dynamic_index_in_dim(
                staging[name], layer, 0, keepdims=False) for name in staging}
        with jax.named_scope("index_score"):
            scores = indexed.index_scores(qi, w, staged["index"], start)
        chosen = indexed.topk_mask(scores, cfg.index_topk)
        with jax.named_scope("attn_indexed"):
            a = indexed.prefill_attention(
                q.reshape(T, KV, H // KV, D), staged["k"], staged["v"],
                chosen, positions, start + T).reshape(T, H * D)
        with jax.named_scope("attn_out"):
            x = x + a @ lp["wo"]["kernel"].astype(cfg.dtype)
        with jax.named_scope("ln_2"):
            h = _rms_norm(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
        f, ids = _ffn(h, lp, cfg, (experts, layer) if experts else None)
        return (x + f, staging), (ids if choices else None)

    (x, staging), ids = lax.scan(body, (x, staging),
                                 (sliced, jnp.arange(cfg.n_layer)))
    return x, staging, ids


def forward_prefill_chunk(params: Params, tokens: jax.Array,
                          cfg: LlamaConfig, start, n_total,
                          staging: Dict[str, jax.Array], state=None,
                          choices: bool = False):
    """One chunk of one prompt (a preset with an index): tokens (1, C), its
    positions ``start .. start + C - 1`` of a prompt of ``n_total``.
    Returns (logits (1, V) at the prompt's last position where this chunk
    holds it (else at the chunk's first), the staging, None: it keeps no
    recurrent state) and, with ``choices``, the experts chosen, (L, C, k)
    int32.

    Under a folded cache the chunk is one window, attends to the folded
    rows of every window before it and to itself causally, and is folded
    at its end where the prompt fills it."""
    if cfg.eva_window:
        x, staging = _run_eva(params, tokens[0], cfg, start, n_total,
                              staging)
        last = jnp.clip(n_total - 1 - start, 0, tokens.shape[1] - 1)
        x = lax.dynamic_slice_in_dim(x, last, 1, axis=0)
        return _head_eva(params, x, cfg), staging, None
    x, staging, ids = _run_indexed(params, tokens[0], cfg, start, staging,
                                   choices)
    last = jnp.clip(n_total - 1 - start, 0, tokens.shape[1] - 1)
    x = lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    logits = _head(params, _final_norm(params, x, cfg), cfg)
    return (logits, staging, None, *([ids] if choices else []))


def _forward_prefill_indexed(params, tokens, cfg, last_pos, choices):
    """:func:`forward_prefill` of a preset with an index: each prompt ONE
    run from an empty staging, the code its chunks run.  K comes back with
    the index key as one more head, (L, B, T, KV + 1, D): its first
    ``index_dim`` lanes, zeros behind them."""
    B, T = tokens.shape
    KV, D = cfg.n_kv_head, cfg.head_dim
    padded = T + -T % (512 if T > 256 else 8)        # whole tiles
    empty = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         prefill_staging(cfg, padded))
    out = []
    for b in range(B):
        toks = jnp.pad(tokens[b], (0, padded - T))
        x, staged, ids = _run_indexed(params, toks, cfg, 0, empty, choices)
        x = x[:T] if last_pos is None \
            else lax.dynamic_slice_in_dim(x, last_pos, 1, axis=0)
        logits = _head(params, _final_norm(params, x, cfg)[None], cfg,
                       only_position=last_pos is not None)[0]
        ks, vs = with_index_head(staged, T, KV, D)
        out.append((logits, ks, vs) + ((ids[:, :T],) if choices else ()))
    logits, ks, vs, *ids = (jnp.stack(part, axis=axis) for part, axis in
                            zip(zip(*out), (0, 1, 1, 1)))
    if ids:
        # (L, B x T, k), a prompt's positions behind another's
        ids = [ids[0].reshape(ids[0].shape[0], B * T, -1)]
    # K stays float32: its own heads hold cfg.dtype's values, the index
    # key's head float32 ones
    return (logits, ks, vs.astype(cfg.dtype), *ids)


def with_index_head(staged: Dict[str, jax.Array], positions: int, n_kv: int,
                    head_dim: int):
    """A staging's first ``positions`` rows as the (ks, vs) a cache
    scatters: ks (L, positions, KV + 1, D), the index key one more head of
    K; vs (L, positions, KV, D)."""
    k, v, index = (staged[name][:, :positions] for name in
                   ("k", "v", "index"))
    lead = k.shape[:2]
    k, v = (a.reshape(*lead, n_kv, head_dim) for a in (k, v))
    index = index[..., None, :head_dim]
    return jnp.concatenate([k, index], axis=2), v


def forward_prefill(params: Params, tokens: jax.Array, cfg: LlamaConfig,
                    last_pos: Optional[jax.Array] = None,
                    choices: bool = False):
    """Prefill forward: tokens (B, T) → (logits, k, v) with
    k/v (L, B, T, KV, D).  Keys are cached post-RoPE, values
    pre-GQA-expand (the paged decode attention expands groups itself) —
    the layout the serve/llm engine scatters into its pool.  With
    ``choices`` (a preset with experts) a fourth result: the experts
    chosen, (L, B x T, k) int32.

    ``last_pos`` (traced scalar): logits only at that position as
    (B, V); None returns the full (B, T, V) — see gpt2.forward_prefill.

    A preset with a ``block_length`` runs under the block-causal mask
    (``_block``); its prompts come in whole blocks and ``last_pos`` is the
    last position of one, whose block's logits come back, (B, block, V).

    Under a folded cache k/v are the rows a sequence HOLDS after the
    prompt, (L, B, held rows, KV, D) float32 (:func:`_forward_eva`)."""
    if cfg.eva_window:
        return _forward_eva(params, tokens, cfg, last_pos, heads=False)
    if cfg.index_topk:
        return _forward_prefill_indexed(params, tokens, cfg, last_pos,
                                        choices)
    x = _embed(params, tokens, cfg)
    sliced, experts = _split_experts(params["blocks"], cfg)

    def body(carry, xs):
        lp, layer = xs
        return _block(carry, lp, cfg, collect_kv=True, choices=choices,
                      served=(experts, layer))

    if experts is None:
        # a dense model: the scan it had, over the stack alone
        x, kept = lax.scan(lambda carry, lp: _block(
            carry, lp, cfg, collect_kv=True, choices=choices),
            x, params["blocks"])
    else:
        x, kept = lax.scan(body, x, (sliced, jnp.arange(cfg.n_layer)))
    x = _final_norm(params, x, cfg)
    if last_pos is not None and cfg.block_length > 1:
        # the logits of the whole block that ``last_pos`` ends, (B, block,
        # V): each position's own token is decided there
        x = lax.dynamic_slice_in_dim(x, last_pos - (cfg.block_length - 1),
                                     cfg.block_length, axis=1)
        return _head(params, x, cfg), *kept
    if last_pos is not None:
        x = lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    return (_head(params, x, cfg, only_position=last_pos is not None),
            *kept)


def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array, cfg: LlamaConfig,
                   choices: bool = False,
                   live: Optional[jax.Array] = None,
                   index_pool: Optional[jax.Array] = None):
    """One decode step over the engine's paged KV pool, handed whole to
    ``ops/paged_attention`` with the layer's index (read-only here).

    Returns (logits (B, V) f32, new_k (L, B, KV, D), new_v (L, B, KV, D))
    and, with ``choices``, the experts chosen, (L, B, k) int32 (``live``
    (B,) bool: the rows that are not padding, see ``_ffn``).

    A preset with an index is handed ``index_pool`` (L, 1, N, bs, F), the
    index plane, read-only: a layer scores every cached position of a row
    and the new token's own, takes the exact ``index_topk`` of them and
    attends to those positions' rows and to no other.  ``new_k`` is then
    (L, B, KV + 1, D) float32: the new token's index key its last head."""
    from ray_tpu.ops.paged_attention import paged_attention_decode
    if tokens.ndim == 2:
        return _forward_decode_blocks(params, tokens, positions, kv_pool,
                                      block_tables, ctx_lens, cfg, choices,
                                      live)
    if cfg.eva_window:
        return _forward_decode_eva(params, tokens, positions, kv_pool,
                                   block_tables, ctx_lens, cfg)
    B = tokens.shape[0]
    E = cfg.n_head * cfg.head_dim
    x = _embed(params, tokens, cfg)                             # (B, E)

    def body(carry, xs):
        x = carry
        lp, layer = xs
        with jax.named_scope("ln_1"):
            h = _rms_norm(x, lp["attn_norm"]["scale"], cfg.rms_eps)
        q, k, v = _qkv(h, lp, cfg)
        with jax.named_scope("rope"):
            q = _rope_at(q, positions, cfg.rope_theta)
            k = _rope_at(k, positions, cfg.rope_theta)
        if cfg.index_topk:
            a, k = _attend_indexed(x, lp, cfg, q, k, v, positions, kv_pool,
                                   index_pool, layer, block_tables, ctx_lens)
        else:
            a = paged_attention_decode(q, kv_pool, layer, block_tables,
                                       ctx_lens, k, v)
        a = a.reshape(B, E)
        with jax.named_scope("attn_out"):
            x = x + a @ lp["wo"]["kernel"].astype(cfg.dtype)
        with jax.named_scope("ln_2"):
            h = _rms_norm(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
        f, ids = _ffn(h, lp, cfg, (experts, layer), live)
        return x + f, ((k, v, ids) if choices else (k, v))

    sliced, experts = _split_experts(params["blocks"], cfg)
    x, kept = lax.scan(body, x, (sliced, jnp.arange(cfg.n_layer)))
    return (_head(params, _final_norm(params, x, cfg), cfg), *kept)


def _attend_indexed(x, lp, cfg, q, k, v, positions, kv_pool, index_pool,
                    layer, block_tables, ctx_lens):
    """A decode step's attention in one layer under the index: the score
    pass over the row's index keys, the exact cut, the walk over the chosen
    positions.  Returns (the heads' output (B, H, D), K with the new
    token's index key as one more head, (B, KV + 1, D) float32: K's own
    heads hold cfg.dtype's values, the key's head float32 ones)."""
    from ray_tpu.ops import indexed_attention as indexed
    from ray_tpu.ops.paged_attention import indexed_attention_decode
    qi, ki, w = _index_proj(x, lp, cfg, positions)
    with jax.named_scope("index_score"):
        scores = indexed.decode_scores(qi, w, index_pool, layer,
                                       block_tables, ctx_lens, ki)
    with jax.named_scope("index_topk"):
        rows, count = indexed.top_positions(
            scores, ctx_lens, cfg.index_topk,
            indexed.pool_rows(block_tables, kv_pool.shape[3]))
    with jax.named_scope("attn_indexed"):
        a = indexed_attention_decode(q, kv_pool, layer, block_tables,
                                     ctx_lens, k, v, None, count, rows)
    head = jnp.pad(ki, ((0, 0), (0, cfg.head_dim - cfg.index_dim)))
    return a, jnp.concatenate([k.astype(jnp.float32), head[:, None]], axis=1)


def _forward_decode_blocks(params, tokens, positions, kv_pool, block_tables,
                           ctx_lens, cfg, choices, live):
    """One pass over a block of positions a row (a preset with a
    ``block_length``): tokens (R, B), the row's block as fed, a mask id
    where a position is undecided; positions (R,) = ctx_lens, the block's
    first position, the committed positions before it being what the pool
    holds.  The block's B queries read the row's pages once and see all B
    new keys and values, in both directions
    (``ops/paged_attention.paged_attention_decode``, the block form); the
    pool is read-only here, and the block's K/V is written by whoever calls,
    for the rows whose pass commits.

    Returns (logits (R, B, V) f32, new_k, new_v (L, R, B, KV, D)) and, with
    ``choices``, the experts chosen, (L, R x B, k) int32 (``live`` (R,))."""
    from ray_tpu.ops.paged_attention import paged_attention_decode
    R, B = tokens.shape
    x = _embed(params, tokens, cfg)                             # (R, B, E)
    at = (positions[:, None] + jnp.arange(B)).reshape(-1)       # (R B,)
    if live is not None:
        live = jnp.repeat(live, B)

    def rope(a):
        flat = a.reshape(R * B, *a.shape[2:])
        return _rope_at(flat, at, cfg.rope_theta).reshape(a.shape)

    def body(carry, xs):
        x = carry
        lp, layer = xs
        with jax.named_scope("ln_1"):
            h = _rms_norm(x, lp["attn_norm"]["scale"], cfg.rms_eps)
        q, k, v = _qkv(h, lp, cfg)
        with jax.named_scope("rope"):
            q, k = rope(q), rope(k)
        with jax.named_scope("attn_block"):
            a = paged_attention_decode(q, kv_pool, layer, block_tables,
                                       ctx_lens, k, v).reshape(R, B, -1)
        with jax.named_scope("attn_out"):
            x = x + a @ lp["wo"]["kernel"].astype(cfg.dtype)
        with jax.named_scope("ln_2"):
            h = _rms_norm(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
        f, ids = _ffn(h, lp, cfg, (experts, layer), live)
        return x + f, ((k, v, ids) if choices else (k, v))

    sliced, experts = _split_experts(params["blocks"], cfg)
    x, kept = lax.scan(body, x, (sliced, jnp.arange(cfg.n_layer)))
    return (_head(params, _final_norm(params, x, cfg), cfg), *kept)


# ----------------------------------------------------------- a folded cache
def _norm(x: jax.Array, scale: jax.Array, cfg: LlamaConfig) -> jax.Array:
    return _rms_norm(x, scale + 1.0 if cfg.norm_offset else scale,
                     cfg.rms_eps)


def _head_eva(params: Params, x: jax.Array, cfg: LlamaConfig,
              heads: bool = False) -> jax.Array:
    """The stream (rows, E) -> float32 logits: head 0's, (rows, V), what a
    serving program returns; with ``heads`` every head's, (rows, pred_heads,
    V).  The products are ``cfg.dtype``'s, the sums float32."""
    with jax.named_scope("ln_f"):
        x = _norm(x, params["norm_f"]["scale"], cfg).astype(cfg.dtype)
    with jax.named_scope("lm_head"):
        w = params["lm_head"]["kernel"].astype(cfg.dtype)
        if not heads:
            w = w[:, :cfg.vocab_size]
        logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return logits.reshape(x.shape[0], -1, cfg.vocab_size) if heads \
            else logits


def _eva_block(x: jax.Array, lp: Params, cfg: LlamaConfig,
               positions: jax.Array, attend):
    """One block on a stream of rows (rows, E), each at its own position:
    ``attend(q (rows, H, D), k, v (rows, KV, D))`` -> (the heads' output,
    what the caller keeps of the layer).  A prompt's chunk and a decode
    step run this body and differ in ``attend`` alone."""
    with jax.named_scope("ln_1"):
        h = _norm(x, lp["attn_norm"]["scale"], cfg).astype(cfg.dtype)
    q, k, v = _qkv(h, lp, cfg)
    with jax.named_scope("rope"):
        q = _rope_at(q, positions, cfg.rope_theta)
        k = _rope_at(k, positions, cfg.rope_theta)
    a, kept = attend(q, k, v)
    with jax.named_scope("attn_out"):
        x = x + (a.reshape(x.shape[0], -1)
                 @ lp["wo"]["kernel"].astype(cfg.dtype)).astype(x.dtype)
    with jax.named_scope("ln_2"):
        h = _norm(x, lp["mlp_norm"]["scale"], cfg).astype(cfg.dtype)
    f, _ = _ffn(h, lp, cfg)
    return x + f.astype(x.dtype), kept


def _stream(params: Params, tokens: jax.Array, cfg: LlamaConfig):
    return _embed(params, tokens, cfg).astype(
        jnp.float32 if cfg.residual_f32 else cfg.dtype)


def _run_eva(params: Params, tokens: jax.Array, cfg: LlamaConfig, start,
             n_total, staging: Dict[str, jax.Array]):
    """One window of one prompt through every layer: tokens (W,), the
    positions ``start .. start + W - 1`` (``start`` whole windows) of a
    prompt of ``n_total``, those past its end padding.  ``staging`` as
    :func:`prefill_staging` says: rows ``0 .. base - 1`` the folded rows of
    the windows before, ``base = start / W x (W / chunk)``; this window's
    K/V goes to rows ``base ..`` and its queries attend causally IN THOSE
    COORDINATES: a folded row lies before every query, an exact one before
    the queries at or after its own position, so one causal kernel serves
    (``ops/window_attention.chunk_attention``).  Where the prompt fills the
    window its fold then takes the place of its first ``W / chunk`` rows,
    and the next window's rows overwrite the rest.  Returns (the stream (W,
    E), the staging)."""
    from ray_tpu.ops.eva_attention import fold_rows
    from ray_tpu.ops.window_attention import chunk_attention
    T = tokens.shape[0]
    H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    per = cfg.eva_window // cfg.eva_chunk
    base = start // cfg.eva_window * per
    positions = start + jnp.arange(T, dtype=jnp.int32)
    full = n_total >= start + T
    rows = staging["k"].shape[1]
    segment = 512 if rows % 512 == 0 else per
    chunk_of = jnp.arange(rows // segment, dtype=jnp.int32)
    f32 = jnp.float32

    def body(carry, xs):
        x, staging = carry
        lp, layer = xs

        def attend(q, k, v):
            with jax.named_scope("kv_stage"):
                new = {"k": k.reshape(T, KV * D).astype(f32),
                       "v": v.reshape(T, KV * D).astype(f32)}
                staged = {name: lax.dynamic_update_slice(
                    staging[name], new[name][None], (layer, base, 0))
                    for name in new}
                mine = {name: lax.dynamic_index_in_dim(
                    staged[name], layer, 0, keepdims=False) for name in new}
            with jax.named_scope("attn_eva"):
                a = chunk_attention(q.reshape(T, KV, H // KV, D), mine["k"],
                                    mine["v"], base, chunk_of)
            kf, vf = fold_rows(k, v, lp["eva"]["phi"], lp["eva"]["mu"],
                               cfg.eva_chunk)
            with jax.named_scope("eva_fold"):
                folded = {"k": kf, "v": vf}
                staged = {name: lax.dynamic_update_slice(
                    staged[name], jnp.where(
                        full, folded[name].reshape(per, KV * D),
                        new[name][:per])[None], (layer, base, 0))
                    for name in new}
            return a, staged

        x, staging = _eva_block(x, lp, cfg, positions, attend)
        return (x, staging), None

    (x, staging), _ = lax.scan(
        body, (_stream(params, tokens, cfg), staging),
        (params["blocks"], jnp.arange(cfg.n_layer)))
    return x, staging


def _forward_eva(params, tokens, cfg, last_pos, heads: bool):
    """Whole prompts under a folded cache, each ONE pass of its windows
    from an empty staging, the code its chunks run: tokens (B, T) -> (logits
    (B, T, V), or (B, V) at ``last_pos``, or every head's with ``heads``;
    ks, vs (L, B, held rows, KV, D) float32, the rows a sequence holds after
    the prompt, padded to the staging's)."""
    B, T = tokens.shape
    W = cfg.eva_window
    chunks = -(-T // W)
    spec = prefill_staging(cfg, chunks * W)
    out = []
    for b in range(B):
        toks = jnp.pad(tokens[b], (0, chunks * W - T))
        staging = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        xs = []
        for i in range(chunks):
            x, staging = _run_eva(params, toks[i * W:(i + 1) * W], cfg,
                                  i * W, T, staging)
            xs.append(x)
        x = jnp.concatenate(xs)[:T]
        if last_pos is not None:
            x = lax.dynamic_slice_in_dim(x, last_pos, 1, axis=0)
        logits = _head_eva(params, x, cfg, heads)
        out.append((logits[0] if last_pos is not None else logits, *(
            staging[name].reshape(cfg.n_layer, -1, cfg.n_kv_head,
                                  cfg.head_dim) for name in ("k", "v"))))
    return tuple(jnp.stack(part, axis=axis)
                 for part, axis in zip(zip(*out), (0, 1, 1)))


def _forward_decode_eva(params, tokens, positions, kv_pool, block_tables,
                        ctx_lens, cfg):
    """A decode step under a folded cache: ``positions`` (B,) the positions
    each row has SEEN (its token's own, for RoPE), ``ctx_lens`` (B,) the
    rows it HOLDS in the pool, folded and exact alike: to the query a folded
    pair is one more key and value, so the walk is the plain paged one.
    Returns (head 0's logits (B, V) f32, new_k, new_v (L, B, KV, D))."""
    from ray_tpu.ops.paged_attention import paged_attention_decode

    def body(x, xs):
        lp, layer = xs

        def attend(q, k, v):
            with jax.named_scope("attn_eva"):
                return paged_attention_decode(q, kv_pool, layer, block_tables,
                                              ctx_lens, k, v), (k, v)

        return _eva_block(x, lp, cfg, positions, attend)

    x, kept = lax.scan(body, _stream(params, tokens, cfg),
                       (params["blocks"], jnp.arange(cfg.n_layer)))
    return (_head_eva(params, x, cfg), *kept)


def fold_window(params: Params, cfg: LlamaConfig, kv_pool: jax.Array,
                pages: jax.Array):
    """The fold of one closed window out of the pool: ``pages`` (W / bs,)
    the blocks that hold its rows, in order -> (kf, vf) (L, W / chunk, KV,
    D) float32, every layer's, for whoever writes them
    (``serve/llm/model_runner.py``: into the first of those pages)."""
    from ray_tpu.ops.eva_attention import fold_rows
    L, _, N, bs, F = kv_pool.shape
    KV, D = cfg.n_kv_head, cfg.head_dim
    with jax.named_scope("eva_fold"):
        # the pool as what it is in memory, L x 2 x N x bs rows of F lanes,
        # and the window's rows gathered by number (indexed by block the
        # TPU compiler re-lays the whole pool first: kv_cache.write_rows)
        at = (pages[:, None] * bs + jnp.arange(bs)).reshape(-1)    # (W,)
        at = jnp.arange(2 * L)[:, None] * (N * bs) + at            # (2 L, W)
        rows = kv_pool.reshape(-1, F)[at.reshape(-1)][:, :KV * D]
        rows = rows.reshape(L, 2, -1, KV, D)
    eva = params["blocks"]["eva"]
    return jax.vmap(partial(fold_rows, chunk=cfg.eva_chunk))(
        rows[:, 0], rows[:, 1], eva["phi"], eva["mu"])



def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: LlamaConfig) -> jax.Array:
    """Next-token cross entropy, a scalar.  With experts it also holds the
    two router terms (balance and z, each a mean over layers) at the
    configuration's coefficients, and hands both and the worst expert load
    to the train step's metrics (spmd.report_step_metrics)."""
    inp, tgt = split_batch(batch)
    x, stats = forward_hidden(params, inp, cfg)
    head = params["lm_head"]["kernel"].astype(cfg.dtype)
    with jax.named_scope("lm_head"):
        logits = x @ head
    loss = next_token_nll(logits, tgt)
    if stats is None:
        return loss
    balance, z = stats.balance_loss.mean(), stats.z_loss.mean()
    from ray_tpu.parallel.spmd import report_step_metrics
    report_step_metrics(moe_aux_loss=balance, moe_z_loss=z,
                        moe_load_max_over_mean=stats.load_max_over_mean.max())
    return loss + cfg.router_aux_coef * balance + cfg.router_z_coef * z


# Sharding: attention/MLP matrices split fsdp×tensor; RoPE/norms replicated.
LLAMA_RULES = [
    (r".*wte$",                P("tensor", "fsdp")),
    (r".*blocks/w[qku].*kernel$",  P("pipeline", "fsdp", "tensor")),
    (r".*blocks/wv/kernel$",   P("pipeline", "fsdp", "tensor")),
    (r".*blocks/wo/kernel$",   P("pipeline", "tensor", "fsdp")),
    (r".*blocks/w_gate/kernel$", P("pipeline", "fsdp", "tensor")),
    (r".*blocks/w_up/kernel$", P("pipeline", "fsdp", "tensor")),
    (r".*blocks/w_down/kernel$", P("pipeline", "tensor", "fsdp")),
    (r".*blocks/experts/w_(gate|up)$", P("pipeline", "expert", "fsdp", "tensor")),
    (r".*blocks/experts/w_down$", P("pipeline", "expert", "tensor", "fsdp")),
    (r".*norm.*scale$",        P(None)),
    (r".*lm_head/kernel$",     P("fsdp", "tensor")),
    (r".*", P(None)),
]
