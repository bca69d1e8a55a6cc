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


@jax.jit
def _expert_block(z, gates, w_gate, w_up, w_down):
    """Every expert of the block on every token, weighted by its gate."""
    hidden = jax.nn.silu(jnp.einsum("nd,xdf->nxf", z, w_gate)) \
        * jnp.einsum("nd,xdf->nxf", z, w_up)
    return jnp.einsum("nxf,xfd,nx->nd", hidden, w_down, gates)


def _moe(h, lp, *, k, eps):
    """h (N, E) float32 -> (h + experts, L_balance, L_z) of one layer."""
    z, gates, balance, z_loss = _route(
        h, _f32(lp["mlp_norm"]["scale"]), _f32(lp["router"]["kernel"]),
        k=k, eps=eps)
    ex = lp["experts"]
    y = h
    for at in range(0, gates.shape[-1], EXPERT_BLOCK):
        block = slice(at, at + EXPERT_BLOCK)
        y = y + _expert_block(z, gates[:, block], _f32(ex["w_gate"][block]),
                              _f32(ex["w_up"][block]),
                              _f32(ex["w_down"][block]))
    return y, balance, z_loss


def hidden(params, tokens, settings: dict, *, qk_norm="projection",
           rope="half"):
    """tokens (B, T) -> (final-norm states (B, T, E), L_balance, L_z), the
    two router terms averaged over the layers.  ``settings`` holds the
    config.json keys num_attention_heads, num_key_value_heads,
    num_experts_per_tok, rms_norm_eps and rope_theta."""
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
    balance = z_loss = 0.0
    for layer in range(n_layer):
        lp = jax.tree_util.tree_map(lambda a: a[layer], blocks)
        alp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in attn_keys})
        x = jnp.stack([attn(x[i], alp) for i in range(b)])
        y, bal, zl = _moe(x.reshape(b * t, -1), lp, k=k, eps=eps)
        x = y.reshape(b, t, -1)
        balance, z_loss = balance + bal / n_layer, z_loss + zl / n_layer
    return (_rms_norm(x, _f32(params["norm_f"]["scale"]), eps),
            balance, z_loss)


def logits(params, tokens, settings: dict, **variant):
    """tokens (B, T) int -> logits (B, T, V) float32."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = hidden(params, tokens, settings, **variant)
        return x @ _f32(params["lm_head"]["kernel"])


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
