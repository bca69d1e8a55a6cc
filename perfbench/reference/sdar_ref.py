"""SDAR-30B-A3B-Chat (JetLM, ``model_type`` ``sdar_moe``: the Qwen3-MoE stack
under a block-causal mask) as published, in plain float32 jax.numpy: one
forward over a sequence as one pass of the model sees it.

No kernel, no sort, no cache, no sharding.  Per layer, with x (T, E) the
residual stream at positions 0..T-1 and B the block length::

    u = RMSNorm(x; g_attn)
    q = u W_q -> (T, H, D)    k = u W_k -> (T, KV, D)    v = u W_v   (no bias)
    q = RMSNorm(q; g_q (D,)) a head    k = RMSNorm(k; g_k (D,)) a head
    q, k = RoPE(q), RoPE(k)      rotate-half over all D lanes, theta
    a_i = sum_j softmax_j(q_i . k_j / sqrt(D)) v_j  over {j: j // B <= i // B}
          H / KV query heads a KV head; D is its own size, H D != E
    h = x + a W_o
    z = RMSNorm(h; g_mlp)    p = softmax(z W_r) over all experts, float32
    S = the k largest (lowest index first on a tie)    w_e = p_e / sum_S p
    y = h + sum_{e in S} w_e W2_e (silu(W1_e z) * W3_e z)

    logits = RMSNorm(y_L; g_f) W_head        untied head, no bias anywhere

The mask is block-causal: a position sees every earlier block whole and its
own block in both directions, blocks counted from position 0 (``B = 1`` is
the causal mask).  What a pass was fed IS the sequence: a position the
program fed the mask id holds the mask id here, and the logits at a
position decide that position's own token (no shift).  The harness hands
``fed`` and no block size, so the block length is the configuration's
(``settings["block_length"]``).

It reads the program's parameter tree (block leaves stacked on a leading
layer axis, the experts' on an expert axis behind it) and nothing else of
the program.  A layer's leaves are cast to float32 as they are used, the
experts a block at a time, and the head runs over ``HEAD_ROWS`` positions at
a time, so a bf16 tree of 4.4 B parameters needs no second copy and
151,936-row logits fit beside it.  Every entry point sets
``jax.default_matmul_precision("highest")``.

Under a program's choice of experts (``logits(..., choices=ids)``; the
serving check, ``perfbench/jobs/serve.py``): every layer still computes its
own p and its own top-k set R, meets the program's set P and goes on UNDER
P, the experts of P weighed by the reference's own p of them over their sum
(the family's rule).  A decision's margin is ``p_(k) - min over e in P of
p_e``, 0 where P is R; ``audit`` counts the ``decisions`` (layers x
positions), those ``differing`` and holds the ``worst_margin``.

``qk_norm="projection"`` and ``mask="causal"`` select deliberately wrong
conventions (OLMoE's norm over the whole projection, which here has no
scale of that width and uses the head's scale tiled; the plain causal
mask): the tests use them to show that either mistake in the program would
be caught.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8        # experts applied at once: (T, 8, width) float32
HEAD_ROWS = 32          # positions of logits computed at once


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x (T, H, D): position t rotates each pair (d, d + D/2) by
    t . theta^(-2d/D)."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_head", "n_kv_head", "head_dim", "eps",
                                   "theta", "block", "qk_norm", "mask"))
def _attention(x, lp, *, n_head, n_kv_head, head_dim, eps, theta, block,
               qk_norm, mask):
    """One sequence: x (T, E) float32 -> x + Wo . attention."""
    t = x.shape[0]
    d = head_dim
    u = _rms_norm(x, lp["attn_norm"]["scale"], eps)
    q, k, v = (u @ lp[w]["kernel"] for w in ("wq", "wk", "wv"))
    if qk_norm == "projection":
        q = _rms_norm(q, jnp.tile(lp["q_norm"]["scale"], n_head), eps)
        k = _rms_norm(k, jnp.tile(lp["k_norm"]["scale"], n_kv_head), eps)
    q, k = q.reshape(t, n_head, d), k.reshape(t, n_kv_head, d)
    if qk_norm == "head":
        q = _rms_norm(q, lp["q_norm"]["scale"], eps)
        k = _rms_norm(k, lp["k_norm"]["scale"], eps)
    v = v.reshape(t, n_kv_head, d)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv_head, axis=1)
    v = jnp.repeat(v, n_head // n_kv_head, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    at = jnp.arange(t) // (1 if mask == "causal" else block)
    scores = jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, n_head * d)
    return x + a @ lp["wo"]["kernel"]


@partial(jax.jit, static_argnames=("k", "eps"))
def _route(h, mlp_scale, w_router, chosen_ids, *, k, eps):
    """h (T, E) -> z, gates (T, X): the reference's own p on the experts
    gone under (``chosen_ids`` (T, K), or its own top-k where None is
    handed as -1s), over their sum, zero elsewhere; per position whether
    the set differs from the reference's own, and the margin."""
    num_experts = w_router.shape[-1]
    z = _rms_norm(h, mlp_scale, eps)
    p = jax.nn.softmax(z @ w_router, axis=-1)
    own_p, own = jax.lax.top_k(p, k)
    under = jnp.where(chosen_ids < 0, own, chosen_ids)
    taken = jax.nn.one_hot(under, num_experts, dtype=jnp.float32).sum(1)
    own_set = jax.nn.one_hot(own, num_experts, dtype=jnp.float32).sum(1)
    differs = jnp.any(taken != own_set, axis=-1)
    margin = own_p[:, -1] - jnp.take_along_axis(p, under, -1).min(-1)
    gates = p * taken
    return z, gates / gates.sum(-1, keepdims=True), differs, margin


@jax.jit
def _expert_block(z, gates, w_gate, w_up, w_down):
    """Every expert of the block on every position, weighted by its gate."""
    hidden = jax.nn.silu(jnp.einsum("nd,xdf->nxf", z, w_gate)) \
        * jnp.einsum("nd,xdf->nxf", z, w_up)
    return jnp.einsum("nxf,xfd,nx->nd", hidden, w_down, gates)


def _moe(h, lp, *, k, eps, chosen):
    norm, router = _f32(lp["mlp_norm"]["scale"]), _f32(lp["router"]["kernel"])
    z, gates, differs, margin = _route(h, norm, router, chosen, k=k, eps=eps)
    ex = lp["experts"]
    y = h
    for at in range(0, gates.shape[-1], EXPERT_BLOCK):
        block = slice(at, at + EXPERT_BLOCK)
        y = y + _expert_block(z, gates[:, block], _f32(ex["w_gate"][block]),
                              _f32(ex["w_up"][block]),
                              _f32(ex["w_down"][block]))
    return y, differs, margin


def hidden(params, tokens, settings: dict, *, choices=None, qk_norm="head",
           mask="block"):
    """tokens (B, T) -> (final-norm states (B, T, E), differs, margin (layers,
    B x T)).  ``settings``: the config.json keys num_attention_heads,
    num_key_value_heads, head_dim, num_experts_per_tok, rms_norm_eps,
    rope_theta, and block_length.  ``choices`` (layers, B x T, K): a
    program's chosen expert ids in the tokens' row-major order."""
    eps, k = float(settings["rms_norm_eps"]), settings["num_experts_per_tok"]
    attn = partial(_attention, n_head=settings["num_attention_heads"],
                   n_kv_head=settings["num_key_value_heads"],
                   head_dim=settings["head_dim"], eps=eps,
                   theta=float(settings["rope_theta"]),
                   block=int(settings["block_length"]), qk_norm=qk_norm,
                   mask=mask)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    x = _f32(params["wte"])[tokens]
    blocks = params["blocks"]
    n_layer = blocks["attn_norm"]["scale"].shape[0]
    attn_keys = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
    if choices is None:
        choices = jnp.full((n_layer, b * t, k), -1, jnp.int32)
    choices = jnp.asarray(choices, jnp.int32)
    if choices.shape != (n_layer, b * t, k):
        raise ValueError(f"choices of shape {choices.shape} for {n_layer} "
                         f"routed layers, {b * t} tokens and {k} a token")
    differs, margins = [], []
    for layer in range(n_layer):
        lp = jax.tree_util.tree_map(lambda a: a[layer], blocks)
        alp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in attn_keys})
        x = jnp.stack([attn(x[i], alp) for i in range(b)])
        y, one, two = _moe(x.reshape(b * t, -1), lp, k=k, eps=eps,
                           chosen=choices[layer])
        x = y.reshape(b, t, -1)
        differs.append(one)
        margins.append(two)
    x = _rms_norm(x, _f32(params["norm_f"]["scale"]), eps)
    return x, jnp.stack(differs), jnp.stack(margins)


def logits(params, tokens, settings: dict, choices=None, **variant):
    """tokens (B, T) int -> logits (B, T, V) float32; under a program's
    ``choices`` (layers, B x T, K) -> (logits, audit)."""
    with jax.default_matmul_precision("highest"):
        x, differs, margin = hidden(params, tokens, settings,
                                    choices=choices, **variant)
        head = _f32(params["lm_head"]["kernel"])
        out = jnp.concatenate([x[:, at:at + HEAD_ROWS] @ head
                               for at in range(0, x.shape[1], HEAD_ROWS)], 1)
    if choices is None:
        return out
    return out, {"decisions": int(differs.size),
                 "differing": int(differs.sum()),
                 "worst_margin": float(margin.max())}
