"""Ling 3.0 (the language model of Ling-3.0-flash-VL) in plain float32
jax.numpy: the full forward over whole sequences, the delta rule token by
token, latent attention un-absorbed, no kernel, no sort, no cache, no
chunk, no batching.  ``perfbench/LING.md`` has the equations with their
sources; in short, with x the residual stream of one sequence (T, E)::

    x_0 = wte[tokens]
    x   = x + Mixer(N1(x));  x = x + F(N2(x))          RMSNorm, pre-norm
    KDA   [q | k | v] = silu(conv(W_qkv u)): depthwise causal over the K
          last inputs, no bias; H heads of D; q = l2norm(q) / sqrt(D), k =
          l2norm(k) a head (x / sqrt(sum x^2 + 1e-6)); beta = sigmoid(W_b
          u) a head; g = lower_bound sigmoid(exp(A_log_h) (W_f u +
          dt_bias)) a channel of a head's keys.  Per head, S (D x D) from
          zero, one token at a time:
              S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t)
              S <- S + k_t d^T;       o_t = S^T q_t
          out = W_o (sigmoid(W_g u)_h * rmsnorm_D(o_t) * o_norm)
    MLA   [c | k_r] = W_kva u; c = rmsnorm(c) * kv_norm; q = W_q u as H x
          (nope | rope); RoPE on the interleaved pairs (2i, 2i + 1) of q_r
          and k_r at theta; [k_n | v]_h = W_kvb,h c; scores (q_n . k_n +
          q_r . k_r) / sqrt(nope + rope), every key up to the query,
          softmax; out = W_o (sigmoid(W_g u)_h * sum p v), by blocks of
          queries
    F dense   W_2 (silu(W_1 h) * W_3 h)
    F routed  s = sigmoid(W_r h) over ALL the router's experts; sel = s +
              expert_bias; the experts in n_group groups by id; a group's
              score the sum of its two largest sel; the topk_group best
              groups stay; the k largest sel among their experts are
              picked; w_e = scale s_e / (sum of the picked s + 1e-20);
              F(h) = SwiGLU_shared(h) + sum over the picked experts THAT
              ARE HELD of w_e SwiGLU_e(h): every held expert on every
              token, masked by the choice, in blocks of experts
    logits = W_head . RMSNorm_final(x_L)               untied, no bias

The share.  The tree holds ``held`` of the router's experts a routed
layer, from ``first_held`` on (``settings``): one chip's share, one
routing group.  A picked expert that is absent adds nothing, here as in
the program; its weight still counts in the sum that normalises the picked
weights.  The eight shares' routed parts and the shared expert once sum to
the uncut layer (``tests/test_ling.py``).

It reads the program's parameter tree (``layers``: one tree a layer;
``wte``, ``ln_f``, ``lm_head``) and nothing else of the program: it
imports nothing from ``ray_tpu``.  A bf16 tree is widened a layer at a
time, the experts a block at a time and the head in slices of the
vocabulary, so that the reference fits beside the engine it checks.  Every
entry point sets ``jax.default_matmul_precision("highest")``.

Under a program's choice of experts (``logits(..., choices=ids)``:
``perfbench/README.md``, a routed family).  With ``choices`` (routed
layers, tokens, k) every routed layer still computes its own selection
scores, its own best groups and its own top-k set R UNDER THE GROUP LIMIT,
meets the program's set P, and goes on UNDER P: the experts of P weighed by
the reference's own sigmoids of them.  A decision has two stages and its
margin is the larger of the two's: the groups (the reference's
``topk_group``-th best group score minus the least score of a group P
touches: <= 0 where P's groups are among the reference's best) and the
experts (the k-th best sel among the experts of P's groups, filled up to
``topk_group`` with the reference's best others, minus the least sel in
P).  ``audit`` counts the ``decisions`` (routed layers x tokens), those
``differing`` (P is not R as a set) and holds the ``worst_margin``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 4        # experts applied at once: (T, 4, width) float32
QUERY_BLOCK = 256       # queries attended at once: (H, 256, T) float32
HEAD_SLICE = 8192       # columns of the head widened at a time
WEIGHT_EPS = 1e-20      # in the picked weights' divisor
L2_EPS = 1e-6
KDA, MLA = "kda", "mla"


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _widened(tree):
    return jax.tree_util.tree_map(_f32, tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _l2norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _rope_pairs(x, theta: float):
    """x (T, ..., D): position t turns pair (2i, 2i + 1) by t theta^(-2i/D)."""
    t, d = x.shape[0], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = angles.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@partial(jax.jit, static_argnames=("n_head", "eps", "lower"))
def _kda(x, lp, *, n_head, eps, lower):
    """One sequence: x (T, E) float32 -> x + KDA(N1(x)), the state stepped
    one token at a time from zero."""
    t = x.shape[0]
    u = _rms_norm(x, lp["norm1"]["scale"], eps)
    qkv = u @ lp["wqkv"]["kernel"]
    taps = lp["conv"]["kernel"]                              # (K, 3 H D)
    k_w = taps.shape[0]
    padded = jnp.pad(qkv, ((k_w - 1, 0), (0, 0)))
    y = _silu(sum(taps[j] * padded[j:j + t] for j in range(k_w)))
    q, k, v = jnp.split(y.reshape(t, 3 * n_head, -1), 3, axis=1)
    d = q.shape[-1]
    q, k = _l2norm(q) / math.sqrt(d), _l2norm(k)
    beta = _sigmoid(u @ lp["wb"]["kernel"])                  # (T, H)
    g = lower * _sigmoid(jnp.exp(lp["a_log"])[:, None] * (
        u @ lp["wf"]["kernel"] + lp["dt_bias"]).reshape(t, n_head, d))

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, :, None] * state               # (H, dk, dv)
        wrong = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[:, :, None] * wrong[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    _, o = jax.lax.scan(token, jnp.zeros((n_head, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms_norm(o, lp["o_norm"]["scale"], eps)
    gate = _sigmoid(u @ lp["wg"]["kernel"])[..., None]
    return x + (o * gate).reshape(t, -1) @ lp["wo"]["kernel"]


@partial(jax.jit, static_argnames=("n_head", "eps", "theta", "lora", "nope"))
def _mla(x, lp, *, n_head, eps, theta, lora, nope):
    """One sequence: x (T, E) float32 -> x + MLA(N1(x)), un-absorbed."""
    t = x.shape[0]
    u = _rms_norm(x, lp["norm1"]["scale"], eps)
    q = (u @ lp["wq"]["kernel"]).reshape(t, n_head, -1)
    kva = u @ lp["wkva"]["kernel"]
    c = _rms_norm(kva[:, :lora], lp["kv_norm"]["scale"], eps)
    q_n, q_r = q[..., :nope], _rope_pairs(q[..., nope:], theta)
    k_r = _rope_pairs(kva[:, lora:], theta)                  # (T, rope)
    kv = (c @ lp["wkvb"]["kernel"]).reshape(t, n_head, -1)
    k_n, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / math.sqrt(q.shape[-1])
    keys = jnp.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):                 # blocks of queries
        rows = jnp.arange(at, min(at + QUERY_BLOCK, t))
        scores = (jnp.einsum("qhd,khd->hqk", q_n[rows], k_n)
                  + jnp.einsum("qhd,kd->hqk", q_r[rows], k_r)) * scale
        probs = jax.nn.softmax(
            jnp.where(keys <= rows[:, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v))
    gate = _sigmoid(u @ lp["wg"]["kernel"])[..., None]
    a = (jnp.concatenate(out) * gate).reshape(t, -1)
    return x + a @ lp["wo"]["kernel"]


def _swiglu(n, ws):
    return (_silu(n @ ws["w1"]) * (n @ ws["w3"])) @ ws["w2"]


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    return x + _swiglu(_rms_norm(x, lp["norm2"]["scale"], eps), lp["mlp"])


def group_limited(select, n_group: int, topk_group: int, first=None):
    """select (T, X) -> (in_group (T, X) bool: the experts of each token's
    ``topk_group`` best groups, the groups' scores (T, G)).  A group's
    score is the sum of its two largest entries.  ``first`` (T, G) bool:
    groups taken before any other, whatever their score (a program's)."""
    t, x = select.shape
    by_group = select.reshape(t, n_group, x // n_group)
    score = jax.lax.top_k(by_group, 2)[0].sum(-1)            # (T, G)
    key = score if first is None else jnp.where(first, jnp.inf, score)
    _, best = jax.lax.top_k(key, topk_group)
    kept = jax.nn.one_hot(best, n_group, dtype=jnp.float32).sum(1) > 0
    return jnp.repeat(kept, x // n_group, axis=1), score


@partial(jax.jit, static_argnames=("k", "eps", "scale", "n_group",
                                   "topk_group"))
def _route(x, ffn_scale, w_router, bias, chosen_ids, *, k, eps, scale,
           n_group, topk_group):
    """x (T, E), a choice of experts (T, K) or None for the reference's own
    -> (n, gates (T, X) over ALL the router's experts: the picked ones'
    weights and zero elsewhere; per token whether the choice differs from
    the reference's own set under the group limit, and the decision's
    margin: the module's head)."""
    num_experts = w_router.shape[-1]
    n = _rms_norm(x, ffn_scale, eps)
    s = _sigmoid(n @ w_router)
    select = s + bias
    in_best, group_score = group_limited(select, n_group, topk_group)
    _, own = jax.lax.top_k(jnp.where(in_best, select, -jnp.inf), k)
    if chosen_ids is None:
        chosen_ids = own
    taken = jax.nn.one_hot(chosen_ids, num_experts, dtype=jnp.float32).sum(1)
    own_set = jax.nn.one_hot(own, num_experts, dtype=jnp.float32).sum(1)
    differs = jnp.any(taken != own_set, axis=-1)
    least = jnp.take_along_axis(select, chosen_ids, -1).min(-1)
    # the groups the program's set touches, and the experts it could have
    # picked given them
    touched = taken.reshape(-1, n_group, num_experts // n_group).sum(-1) > 0
    group_cut = jax.lax.top_k(group_score, topk_group)[0][:, -1]
    group_margin = group_cut - jnp.where(touched, group_score,
                                         jnp.inf).min(-1)
    in_theirs, _ = group_limited(select, n_group, topk_group, first=touched)
    cut = jax.lax.top_k(jnp.where(in_theirs, select, -jnp.inf), k)[0][:, -1]
    margin = jnp.maximum(group_margin, cut - least)
    mine = s * taken
    gates = scale * mine / (mine.sum(-1, keepdims=True) + WEIGHT_EPS)
    return n, gates, differs, margin


@jax.jit
def _expert_block(n, gates, w1, w3, w2):
    """Every expert of the block on every token, weighted by its gate."""
    hidden = _silu(jnp.einsum("nd,xdf->nxf", n, w1)) \
        * jnp.einsum("nd,xdf->nxf", n, w3)
    return jnp.einsum("nxf,xfd,nx->nd", hidden, w2, gates)


@jax.jit
def _close_ffn(x, n, routed, shared):
    return x + routed + _swiglu(n, shared)


def routed_part(n, gates, experts, first_held: int):
    """The held experts' part of a routed layer on normed rows n (T, E):
    sum over the held experts e of gates[:, first_held + e] SwiGLU_e(n);
    ``experts``: the tree's ``{"w1", "w3", "w2"}`` with the held experts
    leading, any float type."""
    held = experts["w1"].shape[0]
    out = jnp.zeros(n.shape, jnp.float32)
    for at in range(0, held, EXPERT_BLOCK):
        block = slice(at, min(at + EXPERT_BLOCK, held))
        mine = gates[:, first_held + block.start:first_held + block.stop]
        out = out + _expert_block(n, mine, *(
            _f32(experts[w][block]) for w in ("w1", "w3", "w2")))
    return out


def route(x, lp, settings: dict, chosen=None):
    """:func:`_route` of one layer's widened tree under ``settings``."""
    return _route(
        x, lp["norm2"]["scale"], lp["router"]["kernel"], lp["expert_bias"],
        chosen, k=settings["num_experts_per_tok"],
        eps=float(settings["rms_norm_eps"]),
        scale=float(settings["routed_scaling_factor"]),
        n_group=settings["n_group"], topk_group=settings["topk_group"])


def hidden(params, tokens, settings: dict, choices=None):
    """tokens (B, T) -> (final-norm states (B, T, E), differs, margin), the
    two last (routed layers, B x T).  ``settings``: ``mixer_types`` (the
    layers HELD, in order), ``first_k_dense_replace``,
    ``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``num_experts_per_tok``, ``n_group``, ``topk_group``,
    ``routed_scaling_factor``, ``rms_norm_eps``, ``rope_theta``,
    ``kda_lower_bound``, ``first_held``.

    ``choices`` (routed layers, B x T, K): a program's chosen expert ids
    for every token in the tokens' row-major order."""
    eps = float(settings["rms_norm_eps"])
    heads = settings["num_attention_heads"]
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    kinds = list(settings["mixer_types"])
    n_dense = settings["first_k_dense_replace"]
    n_routed = len(kinds) - n_dense
    if choices is not None:
        choices = jnp.asarray(choices, jnp.int32)
        if choices.shape[:2] != (n_routed, b * t):
            raise ValueError(f"choices of shape {choices.shape} for "
                             f"{n_routed} routed layers and {b * t} tokens")
        choices = choices.reshape(n_routed, b, t, -1)
    x = _f32(params["wte"][tokens])
    differs, margins = [], []
    for i, kind in enumerate(kinds):
        held = params["layers"][f"l{i:02d}"]
        lp = _widened({n: v for n, v in held.items() if n != "experts"})
        if kind == KDA:
            mixer = partial(_kda, n_head=heads, eps=eps,
                            lower=float(settings["kda_lower_bound"]))
        else:
            mixer = partial(_mla, n_head=heads, eps=eps,
                            theta=float(settings["rope_theta"]),
                            lora=settings["kv_lora_rank"],
                            nope=settings["qk_nope_head_dim"])
        x = jnp.stack([mixer(x[j], lp) for j in range(b)])
        if i < n_dense:
            x = jnp.stack([_dense_ffn(x[j], lp, eps=eps) for j in range(b)])
            continue
        outs = []
        for j in range(b):
            n, gates, differ, margin = route(
                x[j], lp, settings,
                None if choices is None else choices[i - n_dense, j])
            outs.append((_close_ffn(
                x[j], n, routed_part(n, gates, held["experts"],
                                     settings["first_held"]), lp["shared"]),
                differ, margin))
        x = jnp.stack([o[0] for o in outs])
        differs.append(jnp.concatenate([o[1] for o in outs]))
        margins.append(jnp.concatenate([o[2] for o in outs]))
    x = _rms_norm(x, _f32(params["ln_f"]["scale"]), eps)
    return x, jnp.stack(differs), jnp.stack(margins)


def logits(params, tokens, settings: dict, choices=None):
    """tokens (B, T) int -> logits (B, T, V) float32; under a program's
    ``choices`` (routed layers, B x T, K) -> (logits, audit): the
    reference's logits with the chosen experts, and ``decisions``,
    ``differing`` and ``worst_margin`` of the choice in the reference's own
    selection scores under the group limit."""
    with jax.default_matmul_precision("highest"):
        x, differs, margin = hidden(params, tokens, settings, choices)
        head = params["lm_head"]["kernel"]
        out = jnp.concatenate(
            [x @ _f32(head[:, at:at + HEAD_SLICE])
             for at in range(0, head.shape[1], HEAD_SLICE)], axis=-1)
    if choices is None:
        return out
    return out, {"decisions": int(differs.size),
                 "differing": int(differs.sum()),
                 "worst_margin": float(margin.max())}
