"""EvaByte (HKU NLP and SambaNova, 2025-01; ``model_type`` ``evabyte``, 6.5B:
a byte-level Llama-2-7B stack under EVA's chunked linearised attention) in
plain float32 jax.numpy: one forward over a whole sequence, no kernel, no
cache, no chunks of the prompt, no pages.

With ``N(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g)``, ``W`` the window
(2,048), ``c`` the chunk (16), ``w(t) = floor(t / W)`` a position's window and
chunk ``j`` the positions ``c j .. c j + c - 1``; one head written, the rest
alike, ``D`` its width::

    u = N(x; g_attn)
    q_t = RoPE_t(u_t W_q)     k_s = RoPE_s(u_s W_k)     v_s = u_s W_v        no bias; rotate-half over all D lanes
    a_s  = softmax over the c positions s of chunk j of (k_s . phi) / sqrt(D)
    kf_j = sum_s a_s k_s + mu          vf_j = sum_s a_s v_s                   phi, mu: (D,), one a head a layer
    E_t = { s : W w(t) <= s <= t }                                            the query's own window, causal, exact
    C_t = { j : chunk j lies in a window before w(t) }                        every chunk of every closed window
    Z_t = sum_{s in E_t} exp(q_t . k_s / sqrt(D)) + sum_{j in C_t} exp(q_t . kf_j / sqrt(D))
    o_t = (sum_{E_t} exp(q_t . k_s / sqrt(D)) v_s + sum_{C_t} exp(q_t . kf_j / sqrt(D)) vf_j) / Z_t
    h = x + o W_o                      y = h + W_down(silu(W_gate N(h; g_mlp)) * W_up N(h; g_mlp))
    logits_t = N(y_L; g_f) W_head      (pred_heads x vocab): head i scores the byte at t + 1 + i

A layer's scores are built whole, ``(T, T)`` exact and ``(T, T / c)`` folded,
a block of ``QUERY_BLOCK`` queries at a time, masked by ``E_t`` and ``C_t``,
ONE softmax over both.  A window's chunks become visible all at once, when
the window has closed; a chunk of an open window, or a part of a chunk, is
never folded.

Departures from the source, each ``assumed`` in the configuration's file
(``perfbench/configs/evabyte-6.5b.json``, ``perfbench/EVABYTE.md``): the rule
by which ``c`` positions become one is EVA's (Zheng, Yuan, Wang, Kong,
"Efficient Attention via Control Variates", ICLR 2023: an exact softmax over
a local set and, for every other group of keys, ONE pooled key in the same
normaliser and one weighted mean of the group's values) in the form of the
EvaByte release (two learned D-wide vectors a head a layer in place of
sampled random features); its two constants of form, the ``1 / sqrt(D)``
inside a chunk's softmax and the offset ``mu`` on the key's summary alone;
the heads as one ``E x (pred_heads x vocab)`` matrix, head 0 the next byte;
RoPE over halves, pairs ``(d, d + D / 2)``.

It reads the program's parameter tree (block leaves stacked on a leading
layer axis, ``phi`` and ``mu`` under ``blocks["eva"]``, ``(L, H, D)``) and
nothing else of the program.  A layer's leaves are cast to float32 as they
are used.  Every entry point sets ``jax.default_matmul_precision("highest")``.

``fault=`` names deliberately wrong folds, for the tests and
``benchmarks/evabyte_check.py`` to show what a comparison of logits can and
cannot see: ``"uniform_a"`` (a plain mean in place of the chunk's softmax),
``"no_mu"`` (the key's offset left out), ``"drop_newest"`` (the newest
closed window's folded rows left out), ``"early"`` (a window's folded rows
visible one window early, to the queries of that very window, beside its
exact rows) and ``"open_short"`` (the open window cut to its last ``W - 1``
positions: the query at a window's last position loses the first).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
FAULTS = ("uniform_a", "no_mu", "drop_newest", "early", "open_short")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x (T, H, D) at positions 0..T-1, pairs (d, d + D / 2)."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, phi, mu, window, chunk, fault):
    """q, k, v (T, H, D) rotated; phi, mu (H, D) -> (T, H, D)."""
    t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    j = t // chunk                                   # whole chunks
    kc = k[:j * chunk].reshape(j, chunk, h, d)
    vc = v[:j * chunk].reshape(j, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("jchd,hd->jch", kc, phi) * scale, axis=1)
    if fault == "uniform_a":
        a = jnp.full_like(a, 1.0 / chunk)
    kf = jnp.einsum("jch,jchd->jhd", a, kc) + (0.0 if fault == "no_mu"
                                               else mu)
    vf = jnp.einsum("jch,jchd->jhd", a, vc)
    s_at = jnp.arange(t)
    chunk_window = jnp.arange(j) * chunk // window
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        at = jnp.arange(lo, min(lo + QUERY_BLOCK, t))
        mine = at // window
        exact = (s_at[None, :] <= at[:, None]) \
            & (s_at[None, :] // window == mine[:, None])
        if fault == "open_short":
            exact &= s_at[None, :] > at[:, None] - (window - 1)
        folded = chunk_window[None, :] < mine[:, None]
        if fault == "drop_newest":
            folded = chunk_window[None, :] < mine[:, None] - 1
        if fault == "early":
            folded = (chunk_window[None, :] <= mine[:, None]) & (
                (jnp.arange(j)[None, :] + 1) * chunk <= at[:, None] + 1)
        scores = jnp.concatenate([
            jnp.where(exact[None], jnp.einsum("thd,shd->hts", q[at], k),
                      -jnp.inf),
            jnp.where(folded[None], jnp.einsum("thd,jhd->htj", q[at], kf),
                      -jnp.inf)], axis=-1) * scale
        p = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hts,shd->thd", p[..., :t], v)
                   + jnp.einsum("htj,jhd->thd", p[..., t:], vf))
    return jnp.concatenate(out)


def hidden(params, tokens, settings: dict, fault=None):
    """tokens (B, T) int -> the final-norm stream (B, T, E) float32."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no such fault {fault!r}: {FAULTS}")
    heads, eps = settings["num_attention_heads"], settings["rms_norm_eps"]
    window, chunk = settings["window_size"], settings["chunk_size"]
    theta = float(settings["rope_theta"])
    tokens = jnp.asarray(tokens, jnp.int32)
    blocks = params["blocks"]
    n_layer = blocks["attn_norm"]["scale"].shape[0]
    rows = []
    for b in range(tokens.shape[0]):
        x = _f32(params["wte"][tokens[b]])
        t = x.shape[0]
        for layer in range(n_layer):
            lp = jax.tree_util.tree_map(lambda a: _f32(a[layer]), blocks)
            u = _norm(x, lp["attn_norm"]["scale"], eps)
            q, k, v = (
                (u @ lp[w]["kernel"]).reshape(t, heads, -1)
                for w in ("wq", "wk", "wv"))
            o = _attention(_rope(q, theta), _rope(k, theta), v,
                           lp["eva"]["phi"], lp["eva"]["mu"], window, chunk,
                           fault)
            x = x + o.reshape(t, -1) @ lp["wo"]["kernel"]
            z = _norm(x, lp["mlp_norm"]["scale"], eps)
            x = x + (jax.nn.silu(z @ lp["w_gate"]["kernel"])
                     * (z @ lp["w_up"]["kernel"])) @ lp["w_down"]["kernel"]
        rows.append(_norm(x, _f32(params["norm_f"]["scale"]), eps))
    return jnp.stack(rows)


def logits(params, tokens, settings: dict, heads: bool = False, fault=None):
    """tokens (B, T) int -> float32 logits on the host: head 0's (B, T, V),
    what a serving program returns; with ``heads`` every head's, (B, T,
    pred_heads, V)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, settings, fault)
        vocab = settings["vocab_size"]
        head = _f32(params["lm_head"]["kernel"])
        if not heads:
            return np.asarray(x @ head[:, :vocab])
        return np.asarray(x @ head).reshape(*x.shape[:2], -1, vocab)
