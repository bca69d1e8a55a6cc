"""LFM2-MoE as published (Liquid AI; ``model_type`` ``lfm2_moe``,
``transformers``' ``modeling_lfm2_moe.py``), in plain float32 jax.numpy: the
full forward over whole sequences, no kernel, no sort, no cache, no bucket,
no batching.

Per layer, with x the residual stream of one sequence (T, E)::

    u = RMSNorm_operator(x)
    conv layer       [B | C | x~] = W_in u (three segments of E, in that
                     order);  z = B * x~;  c_t = sum_{j<K} w[j] *
                     z_{t-(K-1)+j}: three shifted products, zeros before
                     the sequence, no bias, no activation;
                     h = x + W_out (C * c)
    attention layer  q, k, v = W_q u, W_k u, W_v u as H / KV / KV heads of
                     D; RMSNorm over each head's D dimensions of q and of k
                     (a learned scale of D each) BEFORE RoPE; RoPE
                     rotate-half (pairs (d, d + D/2)) at theta over all D;
                     causal softmax(q k^T / sqrt(D)) v, a KV head serving
                     H / KV query heads, by blocks of queries;
                     h = x + W_o a
    n = RMSNorm_ffn(h)
    dense layer      y = h + W_2 (silu(W_1 n) * W_3 n)
    routed layer     s = sigmoid(W_g n), float32; the k chosen are the top
                     of s + expert_bias; w_e = scale * s_e / (sum of the
                     chosen s + 1e-6)  (norm_topk_prob true);
                     y = h + sum_e w_e W_2,e (silu(W_1,e n) * W_3,e n):
                     every expert is applied to every token and masked by
                     the choice, in blocks of experts.  No shared expert.

    logits = wte . RMSNorm_embedding(x_L)     tied head, no bias anywhere

Departures from the published code, each also under ``assumed`` in the
configuration's file: weights are random (the program's ``init_params``);
the conv's weight is held ``(K, E)`` (the published conv1d's ``(E, 1, K)``
transposed: index K-1 takes the current token either way); the head is the
embedding (the family's convention; the catalog row does not carry
``tie_word_embeddings``); ``head_dim`` is ``hidden_size /
num_attention_heads``.

It reads the program's parameter tree (``dense``: one tree a leading dense
layer; ``periods``: one tree a position of the period of mixers, its
leaves stacked on a leading axis of periods; ``tail``: one tree a routed
layer behind the last whole period) and nothing else of the
program: it imports nothing from ``ray_tpu``.  A bf16 tree is widened a
layer at a time, the experts a block at a time and the head in slices of
the vocabulary, so that the reference fits beside the engine it checks
(one routed layer's experts are 2.4 GB in float32).  Every entry point
sets ``jax.default_matmul_precision("highest")``: on a TPU a float32
matmul runs in lower precision without it.

Under a program's choice of experts (``logits(..., choices=ids)``: the
serving check, ``perfbench/jobs/serve.py``; ``olmoe_ref.py`` says why).
With ``choices`` (routed layers, tokens, k) every routed layer still
computes its own selection scores ``s + expert_bias`` and its own top-k set
R, meets the program's set P, and goes on UNDER P: the experts of P weighed
by the reference's own sigmoids of them under the rule above.  A decision's
margin is ``(s + bias)_(k) - min over e in P of (s + bias)_e``; ``audit``
counts the ``decisions`` (routed layers x tokens), those ``differing`` (P
is not R as a set) and holds the ``worst_margin``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8        # experts applied at once: (T, 8, width) float32
QUERY_BLOCK = 256       # queries attended at once: (H, 256, T) float32
HEAD_SLICE = 16384      # rows of the embedding widened at a time
WEIGHT_EPS = 1e-6       # the family's, in the chosen weights' divisor
CONV, ATTN = "conv", "full_attention"


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _widened(tree):
    return jax.tree_util.tree_map(_f32, tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta: float):
    """x (T, H, D): position t rotates pair (d, d + D/2) by t theta^(-2d/D)."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps",))
def _conv_mixer(x, lp, *, eps):
    """One sequence: x (T, E) float32 -> x + W_out (C * conv(B * x~))."""
    t = x.shape[0]
    u = _rms_norm(x, lp["operator_norm"]["scale"], eps)
    b, c, xt = jnp.split(u @ lp["conv_in"]["kernel"], 3, axis=-1)
    w = lp["conv"]["kernel"]                                  # (K, E)
    width = w.shape[0]
    z = jnp.concatenate([jnp.zeros((width - 1, b.shape[-1]), jnp.float32),
                         b * xt])
    conv = sum(w[j] * z[j:j + t] for j in range(width))
    return x + (c * conv) @ lp["conv_out"]["kernel"]


@partial(jax.jit, static_argnames=("n_head", "n_kv_head", "eps", "theta"))
def _attention(x, lp, *, n_head, n_kv_head, eps, theta):
    """One sequence: x (T, E) float32 -> x + W_o . attention."""
    t = x.shape[0]
    u = _rms_norm(x, lp["operator_norm"]["scale"], eps)
    q = (u @ lp["wq"]["kernel"]).reshape(t, n_head, -1)
    k = (u @ lp["wk"]["kernel"]).reshape(t, n_kv_head, -1)
    v = (u @ lp["wv"]["kernel"]).reshape(t, n_kv_head, -1)
    d = q.shape[-1]
    q = _rope(_rms_norm(q, lp["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, lp["k_norm"]["scale"], eps), theta)
    k = jnp.repeat(k, n_head // n_kv_head, axis=1)
    v = jnp.repeat(v, n_head // n_kv_head, axis=1)
    out = []
    for at in range(0, t, QUERY_BLOCK):                 # blocks of queries
        rows = jnp.arange(at, min(at + QUERY_BLOCK, t))
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(d)
        seen = jnp.arange(t)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v))
    a = jnp.concatenate(out).reshape(t, n_head * d)
    return x + a @ lp["wo"]["kernel"]


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, lp, *, eps):
    n = _rms_norm(h, lp["ffn_norm"]["scale"], eps)
    return h + (_silu(n @ lp["w1"]["kernel"]) * (n @ lp["w3"]["kernel"])) \
        @ lp["w2"]["kernel"]


@partial(jax.jit, static_argnames=("k", "eps", "scale"))
def _route(h, ffn_scale, w_router, bias, chosen_ids, *, k, eps, scale):
    """h (T, E), a choice of experts (T, K) or None for the reference's
    own -> (n, gates (T, X): the chosen experts' weights and zero
    elsewhere; per token whether the choice differs from the reference's
    own top-k set, and the margin (s + bias)_(k) - min (s + bias)[chosen])."""
    num_experts = w_router.shape[-1]
    n = _rms_norm(h, ffn_scale, eps)
    s = jax.nn.sigmoid(n @ w_router)
    select = s + bias
    own_cut, own = jax.lax.top_k(select, k)
    if chosen_ids is None:
        chosen_ids = own
    taken = jax.nn.one_hot(chosen_ids, num_experts, dtype=jnp.float32).sum(1)
    own_set = jax.nn.one_hot(own, num_experts, dtype=jnp.float32).sum(1)
    differs = jnp.any(taken != own_set, axis=-1)
    margin = own_cut[:, -1] \
        - jnp.take_along_axis(select, chosen_ids, -1).min(-1)
    mine = s * taken
    gates = scale * mine / (mine.sum(-1, keepdims=True) + WEIGHT_EPS)
    return n, gates, differs, margin


@jax.jit
def _expert_block(n, gates, w1, w3, w2):
    """Every expert of the block on every token, weighted by its gate."""
    hidden = _silu(jnp.einsum("nd,xdf->nxf", n, w1)) \
        * jnp.einsum("nd,xdf->nxf", n, w3)
    return jnp.einsum("nxf,xfd,nx->nd", hidden, w2, gates)


def _routed_ffn(h, lp, experts, *, k, eps, scale, chosen):
    """h (T, E) -> (h + experts, differs (T,), margin (T,)); ``experts``:
    block -> that block's (w1, w3, w2), float32."""
    n, gates, differs, margin = _route(
        h, lp["ffn_norm"]["scale"], lp["router"]["kernel"],
        lp["expert_bias"], chosen, k=k, eps=eps, scale=scale)
    y = h
    for at in range(0, gates.shape[-1], EXPERT_BLOCK):
        block = slice(at, at + EXPERT_BLOCK)
        y = y + _expert_block(n, gates[:, block], *experts(block))
    return y, differs, margin


def _layers(params, settings: dict):
    """Each held layer in order: (kind, routed, its leaves but the
    experts', float32; block -> the experts' float32 leaves, or None)."""
    n_dense = settings["num_dense_layers"]
    periods = params["periods"]
    scanned = len(periods) * jax.tree_util.tree_leaves(periods)[0].shape[0]
    for i, kind in enumerate(settings["layer_types"]):
        if i < n_dense:
            yield kind, False, _widened(params["dense"][f"d{i}"]), None
            continue
        if i - n_dense < scanned:
            at, j = divmod(i - n_dense, len(periods))
            held = periods[f"p{j}"]
        else:
            at, held = None, params["tail"][f"t{i - n_dense - scanned}"]
        lp = _widened(jax.tree_util.tree_map(
            lambda a: a if at is None else a[at],
            {n: v for n, v in held.items() if n != "experts"}))
        ex = held["experts"]

        def experts(block, ex=ex, at=at):
            return tuple(_f32(ex[w][block] if at is None
                              else ex[w][at, block])
                         for w in ("w1", "w3", "w2"))
        yield kind, True, lp, experts


def hidden(params, tokens, settings: dict, choices=None):
    """tokens (B, T) -> (final-norm states (B, T, E), differs, margin), the
    two last (routed layers, B x T): per routed layer and token whether the
    choice is the reference's own set, and its margin (all False and 0
    under the reference's own choice).  ``settings`` holds the config.json
    keys num_attention_heads, num_key_value_heads, num_experts_per_tok,
    num_dense_layers, norm_eps, rope_theta, routed_scaling_factor, and
    ``layer_types``: the mixers of the layers HELD, in order.

    ``choices`` (routed layers, B x T, K): a program's chosen expert ids
    for every token in the tokens' row-major order."""
    eps, k = float(settings["norm_eps"]), settings["num_experts_per_tok"]
    attn = partial(_attention, n_head=settings["num_attention_heads"],
                   n_kv_head=settings["num_key_value_heads"], eps=eps,
                   theta=float(settings["rope_theta"]))
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    n_routed = len(settings["layer_types"]) - settings["num_dense_layers"]
    if choices is not None:
        choices = jnp.asarray(choices, jnp.int32)
        if choices.shape[:2] != (n_routed, b * t):
            raise ValueError(f"choices of shape {choices.shape} for "
                             f"{n_routed} routed layers and {b * t} tokens")
        choices = choices.reshape(n_routed, b, t, -1)
    x = _f32(params["wte"][tokens])
    differs, margins = [], []
    routed_seen = 0
    for kind, routed, lp, experts in _layers(params, settings):
        mixer = attn if kind == ATTN else partial(_conv_mixer, eps=eps)
        x = jnp.stack([mixer(x[i], lp) for i in range(b)])
        if not routed:
            x = jnp.stack([_dense_ffn(x[i], lp, eps=eps) for i in range(b)])
            continue
        outs = [_routed_ffn(
            x[i], lp, experts, k=k, eps=eps,
            scale=float(settings["routed_scaling_factor"]),
            chosen=None if choices is None else choices[routed_seen, i])
            for i in range(b)]
        x = jnp.stack([o[0] for o in outs])
        differs.append(jnp.concatenate([o[1] for o in outs]))
        margins.append(jnp.concatenate([o[2] for o in outs]))
        routed_seen += 1
    x = _rms_norm(x, _f32(params["embedding_norm"]["scale"]), eps)
    return x, jnp.stack(differs), jnp.stack(margins)


def logits(params, tokens, settings: dict, choices=None):
    """tokens (B, T) int -> logits (B, T, V) float32; under a program's
    ``choices`` (routed layers, B x T, K) -> (logits, audit): the
    reference's logits with the chosen experts, and ``decisions``,
    ``differing`` and ``worst_margin`` of the choice in the reference's own
    selection scores."""
    with jax.default_matmul_precision("highest"):
        x, differs, margin = hidden(params, tokens, settings, choices)
        wte = params["wte"]
        out = jnp.concatenate(
            [x @ _f32(wte[at:at + HEAD_SLICE]).T
             for at in range(0, wte.shape[0], HEAD_SLICE)], axis=-1)
    if choices is None:
        return out
    return out, {"decisions": int(differs.size),
                 "differing": int(differs.sum()),
                 "worst_margin": float(margin.max())}
