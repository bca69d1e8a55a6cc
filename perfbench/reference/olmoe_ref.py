"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060) as published, in plain
float32 jax.numpy: forward pass and training loss.

No kernel, no sort, no cache, no remat, no sharding.  Per layer, with x the
residual stream of all B x T tokens::

    u = RMSNorm(x)
    q = RoPE(RMSNorm_q(Wq u))   k = RoPE(RMSNorm_k(Wk u))   v = Wv u
        RMSNorm_q / _k run over the WHOLE projection (hidden-wide, own
        scales) before it is split into heads; RoPE is rotate-half
        (pairs (d, d + D/2)) at theta; causal softmax(q k^T / sqrt(D)) v
    h = x + Wo . attention
    z = RMSNorm(h)    p = softmax(W_r z) over all experts, float32
    y = h + sum_{e in top-k(p)} p_e . W_down,e (silu(W_gate,e z) * (W_up,e z))
        p_e as it is (norm_topk_prob false); every expert is applied to
        every token and masked by the top-k, in blocks of experts

    logits = W_head . RMSNorm(x_L)      untied head, no bias anywhere
    loss = CE + aux . L_balance + zc . L_z
        L_balance = E . sum_e f_e P_e per layer (f_e: share of the N k
        assignments that went to e, P_e: mean of p_e over the N tokens),
        L_z = mean over tokens of logsumexp(W_r z)^2, both averaged over
        the layers

Departures from the published code, each also under the configuration's
``assumed``: the two coefficients are the paper's (0.01 and 0.001; the
catalog row of the config drops them); L_balance is computed per layer
over the tokens of the batch given here and then averaged, where the
released training code concatenates the layers' router logits first (the
same number when every layer sees the same tokens, up to the order of
sums); ``clip_qkv`` is null and absent.

It reads the program's parameter tree (block leaves stacked on a leading
layer axis, the experts' on an expert axis behind it) and nothing else of
the program.  A layer's leaves are cast to float32 as they are used, the
experts a block at a time, so a bf16 tree of 1.5 B parameters needs no
second full copy, and attention runs one sequence at a time.  Every entry
point sets ``jax.default_matmul_precision("highest")``: on a TPU a float32
matmul runs in lower precision without it.

Under a program's choice of experts (``logits(..., choices=ids)``: the
serving check, ``perfbench/jobs/serve.py``).  Where the reference scores two
experts a rounding apart, a program in bfloat16 decides between them either
way and both are correct executions, so a forward under the reference's OWN
top-k is no reference for that program's logits.  With ``choices`` (routed
layers, tokens, k) every layer still computes its own p and its own top-k
set R, meets the program's set P, and goes on UNDER P: the experts of P
weighed by the reference's p of them as they are (the family's rule:
``norm_topk_prob`` false), so that what follows a decision made the other
way stays comparable.  The choice itself is audited in the reference's own
scores: a decision's margin is ``p_(k) - min over e in P of p_e``, how far
under the reference's own cut the program's lowest pick lies (0 where P is
R), and ``audit`` counts the ``decisions`` (routed layers x tokens), those
``differing`` (P is not R as a set) and holds the ``worst_margin``.

``qk_norm`` and ``rope`` select deliberately wrong conventions
(``"head"``: the norm over each head's 128 features; ``"interleaved"``:
pairs (2i, 2i + 1)); the tests use them to show that either mistake in
the program would be caught.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8        # experts applied at once: (N, 8, width) float32


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta: float, convention: str):
    """x (T, H, D): position t rotates each pair by t . theta^(-2i/D)."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    if convention == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_head", "n_kv_head", "eps", "theta",
                                   "qk_norm", "rope"))
def _attention(x, lp, *, n_head, n_kv_head, eps, theta, qk_norm, rope):
    """One sequence: x (T, E) float32 -> x + Wo . attention."""
    t, e = x.shape
    d = e // n_head
    u = _rms_norm(x, lp["attn_norm"]["scale"], eps)
    q, k, v = (u @ lp[w]["kernel"] for w in ("wq", "wk", "wv"))
    if qk_norm == "head":
        q = _rms_norm(q.reshape(t, n_head, d),
                      lp["q_norm"]["scale"].reshape(n_head, d), eps)
        k = _rms_norm(k.reshape(t, n_kv_head, d),
                      lp["k_norm"]["scale"].reshape(n_kv_head, d), eps)
    else:
        q = _rms_norm(q, lp["q_norm"]["scale"], eps).reshape(t, n_head, d)
        k = _rms_norm(k, lp["k_norm"]["scale"], eps).reshape(t, n_kv_head, d)
    v = v.reshape(t, n_kv_head, d)
    q, k = _rope(q, theta, rope), _rope(k, theta, rope)
    if n_kv_head != n_head:
        k = jnp.repeat(k, n_head // n_kv_head, axis=1)
        v = jnp.repeat(v, n_head // n_kv_head, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, e)
    return x + a @ lp["wo"]["kernel"]


@partial(jax.jit, static_argnames=("k", "eps"))
def _route(h, mlp_scale, w_router, *, k, eps):
    """h (N, E) -> z, gates (N, X) with zeros off the top-k, L_balance, L_z."""
    n, num_experts = h.shape[0], w_router.shape[-1]
    z = _rms_norm(h, mlp_scale, eps)
    logits = z @ w_router
    p = jax.nn.softmax(logits, axis=-1)
    _, top = jax.lax.top_k(p, k)
    chosen = jax.nn.one_hot(top, num_experts, dtype=jnp.float32).sum(1)
    share = chosen.sum(0) / (n * k)                         # f_e
    balance = num_experts * jnp.sum(share * p.mean(0))
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return z, p * chosen, balance, z_loss


@partial(jax.jit, static_argnames=("k", "eps"))
def _route_under(h, mlp_scale, w_router, chosen_ids, *, k, eps):
    """h (N, E), the program's choice (N, K) -> z, gates (N, X): the
    reference's own p on the chosen experts and zero elsewhere; and per
    token whether the chosen set differs from the reference's own top-k,
    and the margin p_(k) - min p[chosen]."""
    num_experts = w_router.shape[-1]
    z = _rms_norm(h, mlp_scale, eps)
    p = jax.nn.softmax(z @ w_router, axis=-1)
    own_p, own = jax.lax.top_k(p, k)
    taken = jax.nn.one_hot(chosen_ids, num_experts, dtype=jnp.float32).sum(1)
    own_set = jax.nn.one_hot(own, num_experts, dtype=jnp.float32).sum(1)
    differs = jnp.any(taken != own_set, axis=-1)
    margin = own_p[:, -1] - jnp.take_along_axis(p, chosen_ids, -1).min(-1)
    return z, p * taken, differs, margin


@jax.jit
def _expert_block(z, gates, w_gate, w_up, w_down):
    """Every expert of the block on every token, weighted by its gate."""
    hidden = jax.nn.silu(jnp.einsum("nd,xdf->nxf", z, w_gate)) \
        * jnp.einsum("nd,xdf->nxf", z, w_up)
    return jnp.einsum("nxf,xfd,nx->nd", hidden, w_down, gates)


def _moe(h, lp, *, k, eps, chosen=None):
    """h (N, E) float32 -> (h + experts, L_balance, L_z) of one layer; under
    a program's choice ``chosen`` (N, K) -> (h + experts, differs (N,),
    margin (N,))."""
    norm, router = _f32(lp["mlp_norm"]["scale"]), _f32(lp["router"]["kernel"])
    if chosen is None:
        z, gates, *terms = _route(h, norm, router, k=k, eps=eps)
    else:
        z, gates, *terms = _route_under(h, norm, router, chosen, k=k, eps=eps)
    ex = lp["experts"]
    y = h
    for at in range(0, gates.shape[-1], EXPERT_BLOCK):
        block = slice(at, at + EXPERT_BLOCK)
        y = y + _expert_block(z, gates[:, block], _f32(ex["w_gate"][block]),
                              _f32(ex["w_up"][block]),
                              _f32(ex["w_down"][block]))
    return (y, *terms)


def hidden(params, tokens, settings: dict, *, qk_norm="projection",
           rope="half", choices=None):
    """tokens (B, T) -> (final-norm states (B, T, E), L_balance, L_z), the
    two router terms averaged over the layers.  ``settings`` holds the
    config.json keys num_attention_heads, num_key_value_heads,
    num_experts_per_tok, rms_norm_eps and rope_theta.

    Under ``choices`` (layers, B x T, K), a program's chosen expert ids for
    every token in the tokens' row-major order -> (states, differs, margin):
    per layer and token whether the choice is the reference's own set, and
    its margin (``_route_under``), each (layers, B x T)."""
    eps, k = float(settings["rms_norm_eps"]), settings["num_experts_per_tok"]
    attn = partial(_attention, n_head=settings["num_attention_heads"],
                   n_kv_head=settings["num_key_value_heads"], eps=eps,
                   theta=float(settings["rope_theta"]), qk_norm=qk_norm,
                   rope=rope)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    x = _f32(params["wte"])[tokens]
    blocks = params["blocks"]
    n_layer = blocks["attn_norm"]["scale"].shape[0]
    attn_keys = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
    if choices is not None:
        choices = jnp.asarray(choices, jnp.int32)
        if choices.shape[:2] != (n_layer, b * t):
            raise ValueError(f"choices of shape {choices.shape} for "
                             f"{n_layer} routed layers and {b * t} tokens")
    first, second = [], []      # L_balance and L_z, or differs and margin
    for layer in range(n_layer):
        lp = jax.tree_util.tree_map(lambda a: a[layer], blocks)
        alp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in attn_keys})
        x = jnp.stack([attn(x[i], alp) for i in range(b)])
        y, one, two = _moe(x.reshape(b * t, -1), lp, k=k, eps=eps,
                           chosen=None if choices is None else choices[layer])
        x = y.reshape(b, t, -1)
        first.append(one)
        second.append(two)
    x = _rms_norm(x, _f32(params["norm_f"]["scale"]), eps)
    if choices is not None:
        return x, jnp.stack(first), jnp.stack(second)
    return (x, sum(v / n_layer for v in first),
            sum(v / n_layer for v in second))


def logits(params, tokens, settings: dict, choices=None, **variant):
    """tokens (B, T) int -> logits (B, T, V) float32; under a program's
    ``choices`` (layers, B x T, K) -> (logits, audit): the reference's
    logits with the chosen experts, and ``decisions``, ``differing`` and
    ``worst_margin`` of the choice in the reference's own scores."""
    with jax.default_matmul_precision("highest"):
        x, differs, margin = hidden(params, tokens, settings,
                                    choices=choices, **variant)
        out = x @ _f32(params["lm_head"]["kernel"])
    if choices is None:
        return out
    return out, {"decisions": int(differs.size),
                 "differing": int(differs.sum()),
                 "worst_margin": float(margin.max())}


def loss_terms(params, inputs, targets, settings: dict, **variant):
    """(cross entropy, L_balance, L_z): three float32 scalars."""
    with jax.default_matmul_precision("highest"):
        x, balance, z_loss = hidden(params, inputs, settings, **variant)
        head = _f32(params["lm_head"]["kernel"])
        targets = jnp.asarray(targets, jnp.int32)
        total = 0.0
        for i in range(x.shape[0]):          # one sequence's logits at a time
            logp = jax.nn.log_softmax(x[i] @ head, axis=-1)
            total = total - jnp.take_along_axis(
                logp, targets[i][:, None], -1).sum()
        return total / targets.size, balance, z_loss


def loss(params, inputs, targets, settings: dict, **variant):
    """The training loss: CE + router_aux_loss_coef . L_balance +
    router_z_loss_coef . L_z, a float32 scalar."""
    ce, balance, z_loss = loss_terms(params, inputs, targets, settings,
                                     **variant)
    return ce + settings["router_aux_loss_coef"] * balance \
        + settings["router_z_loss_coef"] * z_loss
