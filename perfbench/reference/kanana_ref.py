"""Kanana-2-30B-A3B's block (``model_type`` ``deepseek_v3``;
kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``, after
``transformers``' ``modeling_deepseek_v3.py``) in plain float32
jax.numpy: forward pass and training loss of one chip's share.

No kernel, no sort, no cache, no remat, no sharding.  Per layer, with x the
residual stream of all B x T tokens::

    u = RMSNorm(x)
    q = W_q u                        H heads of [q_nope | q_rope]; no query
                                     latent (q_lora_rank null), no q norm
    [c | k_rope] = W_kva u           one latent and one rotary key a token
    [k_nope_h | v_h] = W_kvb RMSNorm_c(c)
    RoPE at theta on the interleaved pairs (2i, 2i + 1) of q_rope_h and of
        k_rope, which is the same key for every head; no scaling
    a_h = softmax(q_h [k_nope_h | k_rope]^T / sqrt(nope + rope), causal) v_h
    h = x + W_o [a_1 .. a_H]
    n = RMSNorm(h)
    dense layers (the first ``first_k_dense_replace``):
        y = h + W_down (silu(W_gate n) * (W_up n))
    sparse layers:
        s = sigmoid(W_r n)             over ALL routed experts, float32
        chosen = top-k of (s + b)      b: e_score_correction_bias, in the
                                       choice only; n_group = topk_group
                                       = 1, so there is no group step
        w_e = scale . s_e / (sum of s over the k chosen + 1e-20)
        y = h + Shared(n) + sum over chosen e THAT ARE HELD of w_e Expert_e(n)
    logits = W_head RMSNorm(x_L) over the vocabulary rows held
    loss = mean next-token cross entropy; no auxiliary term (noaux_tc)

**The share is data.**  ``settings["held_expert_ids"]`` lists the routed
experts whose matrices the tree holds, in the tree's order; the router
keeps its full width, a token's weights are normalised over all k it
chose, and what an absent expert would have added is left out (another
chip's part).  The vocabulary is the rows of ``wte`` / columns of
``lm_head`` the tree has: a sliced vocabulary is a smaller vocabulary.

Departures from the published code, each also under the configuration's
``assumed``: RoPE keeps the interleaved layout (the published code
de-interleaves q_rope and k_rope to (all evens | all odds) and rotates
halves: the same pairs at the same angles, and the same scores, because
both sides are permuted alike); ``b`` is held fixed (the rule that moves
it between steps is a training recipe, not a key of the config).

It reads the program's parameter tree (two stacks of block leaves,
``dense_blocks`` and ``moe_blocks``, each on a leading layer axis, the
experts' on an expert axis behind it) and nothing else of the program.  A
layer's leaves are cast to float32 as they are used, the experts one at a
time and every held expert applied to every token, masked by the choice;
attention runs one sequence at a time, by query block.  Every entry point
sets ``jax.default_matmul_precision("highest")``.

``rope="half"`` selects a deliberately wrong convention (rotate halves
without de-interleaving) and ``bias_in_weights=True`` another (the bias
added to the weights too); the tests use them to show that either mistake
in the program would be caught.  ``flip_margin`` > 0 takes a token's
(k+1)-th candidate in place of its k-th wherever their selection scores
lie closer than that: the builder's measure of what a routing decision
that rounding could turn is worth to the loss.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512           # queries scored at once: (H, 512, T) float32


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta: float, convention: str):
    """x (T, H, D): position t turns pair i by t . theta^(-2i/D)."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    if convention == "half":
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@partial(jax.jit, static_argnames=("n_head", "nope", "rope_dim", "v_dim",
                                   "eps", "theta", "rope"))
def _attention(x, lp, *, n_head, nope, rope_dim, v_dim, eps, theta, rope):
    """One sequence: x (T, E) float32 -> x + W_o . attention."""
    t = x.shape[0]
    latent = lp["kv_norm"]["scale"].shape[-1]
    u = _rms_norm(x, lp["attn_norm"]["scale"], eps)
    q = (u @ lp["wq"]["kernel"]).reshape(t, n_head, nope + rope_dim)
    kva = u @ lp["wkv_a"]["kernel"]
    c = _rms_norm(kva[:, :latent], lp["kv_norm"]["scale"], eps)
    kvb = (c @ lp["wkv_b"]["kernel"]).reshape(t, n_head, nope + v_dim)
    k_rope = _rope(kva[:, None, latent:], theta, rope)           # (T, 1, r)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta, rope)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (t, n_head, rope_dim))], -1)
    v = kvb[..., nope:]
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    key_pos = jnp.arange(t)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(nope + rope_dim)
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    a = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, n_head * v_dim)
    return x + a @ lp["wo"]["kernel"]


@jax.jit
def _swiglu(n, w_gate, w_up, w_down):
    return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("k", "scale", "eps", "bias_in_weights",
                                   "flip_margin"))
def _route(h, mlp_scale, w_router, bias, *, k, scale, eps, bias_in_weights,
           flip_margin):
    """h (N, E) -> n, gates (N, X) with zeros off the k chosen, and the
    gap between each token's k-th and (k+1)-th selection score."""
    n = _rms_norm(h, mlp_scale, eps)
    scores = jax.nn.sigmoid(n @ w_router)
    ranked, top = jax.lax.top_k(scores + bias, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    if flip_margin:
        last = jnp.where(margin < flip_margin, top[:, k], top[:, k - 1])
        top = top.at[:, k - 1].set(last)
    chosen = jax.nn.one_hot(top[:, :k], scores.shape[-1],
                            dtype=jnp.float32).sum(1)
    weigh = (scores + bias if bias_in_weights else scores) * chosen
    gates = scale * weigh / (weigh.sum(-1, keepdims=True) + 1e-20)
    return n, gates, margin


def sparse_mlp(h, lp, settings: dict, *, bias_in_weights=False,
               flip_margin=0.0):
    """h (N, E) float32 -> (h + shared expert + the held routed experts'
    part, the tokens' selection margins) of one sparse layer."""
    n, gates, margin = _route(
        h, _f32(lp["mlp_norm"]["scale"]), _f32(lp["router"]["kernel"]),
        _f32(lp["router"]["select_bias"]),
        k=settings["num_experts_per_tok"],
        scale=float(settings["routed_scaling_factor"]),
        eps=float(settings["rms_norm_eps"]),
        bias_in_weights=bias_in_weights, flip_margin=float(flip_margin))
    sh, ex = lp["shared"], lp["experts"]
    y = h + _swiglu(n, *(_f32(sh[w]["kernel"])
                         for w in ("w_gate", "w_up", "w_down")))
    held = settings["held_expert_ids"]
    assert len(held) == ex["w_gate"].shape[0], (len(held), ex["w_gate"].shape)
    for i, e in enumerate(held):             # every held expert, every token
        y = y + gates[:, e:e + 1] * _swiglu(
            n, _f32(ex["w_gate"][i]), _f32(ex["w_up"][i]),
            _f32(ex["w_down"][i]))
    return y, margin


def dense_mlp(h, lp, settings: dict):
    n = _rms_norm(h, _f32(lp["mlp_norm"]["scale"]),
                  float(settings["rms_norm_eps"]))
    return h + _swiglu(n, *(_f32(lp[w]["kernel"])
                            for w in ("w_gate", "w_up", "w_down")))


ATTN_KEYS = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def layer(x, lp, settings: dict, *, sparse: bool, rope="interleaved",
          **variant):
    """One block on x (B, T, E) float32 with its (unstacked) leaves ->
    (out, margins (B T,) | None); ``variant`` is the router's and means
    nothing to a dense layer."""
    b, t, _ = x.shape
    alp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in ATTN_KEYS})
    attn = partial(_attention, n_head=settings["num_attention_heads"],
                   nope=settings["qk_nope_head_dim"],
                   rope_dim=settings["qk_rope_head_dim"],
                   v_dim=settings["v_head_dim"],
                   eps=float(settings["rms_norm_eps"]),
                   theta=float(settings["rope_theta"]), rope=rope)
    h = jnp.stack([attn(x[i], alp) for i in range(b)]).reshape(b * t, -1)
    if sparse:
        y, margin = sparse_mlp(h, lp, settings, **variant)
    else:
        y, margin = dense_mlp(h, lp, settings), None
    return y.reshape(b, t, -1), margin


def hidden(params, tokens, settings: dict, **variant):
    """tokens (B, T) -> (final-norm states (B, T, E), the sparse layers'
    selection margins (layers, B T)).  ``settings`` holds the config.json
    keys num_attention_heads, qk_nope_head_dim, qk_rope_head_dim,
    v_head_dim, num_experts_per_tok, routed_scaling_factor, rms_norm_eps,
    rope_theta, and ``held_expert_ids``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _f32(params["wte"])[tokens]
    margins = []
    for name, sparse in (("dense_blocks", False), ("moe_blocks", True)):
        blocks = params[name]
        for i in range(blocks["attn_norm"]["scale"].shape[0]):
            lp = jax.tree_util.tree_map(lambda a: a[i], blocks)
            x, margin = layer(x, lp, settings, sparse=sparse, **variant)
            if sparse:
                margins.append(margin)
    return (_rms_norm(x, _f32(params["norm_f"]["scale"]),
                      float(settings["rms_norm_eps"])), jnp.stack(margins))


def logits(params, tokens, settings: dict, **variant):
    """tokens (B, T) int -> logits (B, T, rows held) float32."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(params, tokens, settings, **variant)
        return x @ _f32(params["lm_head"]["kernel"])


def loss_and_margins(params, inputs, targets, settings: dict, **variant):
    """(mean next-token cross entropy, margins (layers, B T))."""
    with jax.default_matmul_precision("highest"):
        x, margins = hidden(params, inputs, settings, **variant)
        head = _f32(params["lm_head"]["kernel"])
        targets = jnp.asarray(targets, jnp.int32)
        total = 0.0
        for i in range(x.shape[0]):          # one sequence's logits at a time
            logp = jax.nn.log_softmax(x[i] @ head, axis=-1)
            total = total - jnp.take_along_axis(
                logp, targets[i][:, None], -1).sum()
        return total / targets.size, margins


def loss(params, inputs, targets, settings: dict, **variant):
    """The training loss, a float32 scalar."""
    return loss_and_margins(params, inputs, targets, settings, **variant)[0]
