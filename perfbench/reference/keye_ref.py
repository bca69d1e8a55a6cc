"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, ``model_type``
``KeyeVL2``: the Qwen3-MoE stack whose attention reads the positions a
DeepSeek-Sparse-Attention lightning indexer picks) in plain float32
jax.numpy: one forward over a sequence, no kernel, no cache, no chunks.

Per layer, with x (T, E) the residual stream at positions 0..T-1 and ``u =
RMSNorm(x; g_attn)`` the attention's own input::

    q = RoPE(RMSNorm_head(u W_q))  (T, H, D)    k = RoPE(RMSNorm_head(u W_k))  (T, KV, D)
    v = u W_v  (T, KV, D)                        no bias; rotate-half over all D lanes
    qI = RoPE(u W_Iq)  (T, IH, ID)               kI = RoPE(LayerNorm(u W_Ik))  (T, ID), ONE head
    w  = u W_Iw  (T, IH)
    I_t,s = sum_j w_t,j ReLU(qI_t,j . kI_s)                                   s <= t
    S_t   = the topk positions s <= t of largest I_t,s: all t + 1 of them
            while t + 1 <= topk; of equal scores the lower position first
    a_t,h = sum over s in S_t of softmax_s(q_t,h . k_s,g(h) / sqrt(D)) v_s,g(h)
    h = x + a W_o
    z = RMSNorm(h; g_mlp)    p = softmax(z W_r) over all experts
    R = the k largest (lowest index first on a tie)    w_e = p_e / sum_R p
    y = h + sum_{e in R} w_e W2_e (silu(W1_e z) * W3_e z)

    logits = RMSNorm(y_L; g_f) W_head        untied head

``I`` is whole, (T, T) a layer, computed a block of ``QUERY_BLOCK`` queries
at a time; the cut is ``jax.lax.top_k`` over a query's whole row (exact, the
lower index of equal values first), a zero score +0.0 whatever the weights'
signs made it; the softmax runs over every position under a mask of the
chosen ones.  The reference chooses its OWN positions: nothing of the
program's selection is handed to it.

Departures from the source, each ``assumed`` in the configuration's file:
the RMSNorm a head on q and k (Qwen3's); the indexer's key LayerNorm (scale
and bias, eps 1e-6) and rotary over all ``ID`` lanes at the model's theta
(DeepSeek-V3.2-Exp's indexer norms and rotates its key; no rotary width is
given); positive constant scales of ``w`` or ``I`` left out (they move no
top-k); the release's FP8 and Hadamard rotation of ``qI``, ``kI`` left out;
``q_chunk_size`` / ``kv_chunk_size`` read as the release's tiling of ``I``;
text-only positions, so that the three ids of ``mrope_section`` coincide and
the rotary is RoPE over the 128 lanes; no tower.

It reads the program's parameter tree (block leaves stacked on a leading
layer axis, the experts' on an expert axis behind it, the indexer's under
``blocks["index"]``) and nothing else of the program.  The experts, their
router and the audit under a program's ``choices`` are ``sdar_ref``'s (the
stack is SDAR's to the last width).  A layer's leaves are cast to float32 as
they are used and the head runs a block of rows and of columns at a time,
each block brought to the host, so that a bf16 tree of 4.4 B parameters and
a 6,152 x 151,936 result need no second copy on the device.  Every entry
point sets ``jax.default_matmul_precision("highest")``.

``select=`` names deliberately wrong selections (``"lowest"``: the top-k of
the negated scores; ``"first"``: the first topk positions; ``"short"``:
topk - 1 of them; ``"dense"``: every position): the tests use them to show
what a comparison of logits can and cannot see.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.sdar_ref import _f32, _moe, _rms_norm, _rope

QUERY_BLOCK = 256       # queries whose I and scores are held at once
HEAD_ROWS = 512         # positions of logits computed at once
HEAD_COLS = 4           # in as many blocks of the vocabulary


def _layer_norm(x, scale, bias, eps):
    x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale + bias


def chosen_positions(scores, t, topk: int, select: str = "top"):
    """scores (Q, T) float32 of queries at positions ``t`` (Q,) against
    positions 0..T-1 -> (Q, T) bool: the positions each attends to."""
    at = jnp.arange(scores.shape[1])
    seen = at[None, :] <= t[:, None]
    if select == "dense":
        return seen
    if select == "first":
        return seen & (at[None, :] < topk)
    k = min(topk - (select == "short"), scores.shape[1])
    scores = jnp.where(scores == 0.0, 0.0, scores)          # -0.0 is 0.0
    if select == "lowest":
        scores = -scores
    _, ids = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, ids].set(True) & seen


@partial(jax.jit, static_argnames=("n_head", "n_kv_head", "head_dim", "eps",
                                   "theta", "index_heads", "index_dim",
                                   "topk", "select"))
def _attention(x, lp, *, n_head, n_kv_head, head_dim, eps, theta,
               index_heads, index_dim, topk, select):
    """One sequence: x (T, E) float32 -> (x + Wo . attention, the chosen
    positions (T, T) bool)."""
    t, d = x.shape[0], head_dim
    u = _rms_norm(x, lp["attn_norm"]["scale"], eps)
    q, k, v = (u @ lp[w]["kernel"] for w in ("wq", "wk", "wv"))
    q = _rms_norm(q.reshape(t, n_head, d), lp["q_norm"]["scale"], eps)
    k = _rms_norm(k.reshape(t, n_kv_head, d), lp["k_norm"]["scale"], eps)
    v = v.reshape(t, n_kv_head, d)
    q, k = _rope(q, theta), _rope(k, theta)
    ix = lp["index"]
    qi = _rope((u @ ix["wq"]["kernel"]).reshape(t, index_heads, index_dim),
               theta)
    ki = _layer_norm(u @ ix["wk"]["kernel"], ix["index_norm"]["scale"],
                     ix["index_norm"]["bias"], eps)
    ki = _rope(ki[:, None], theta)[:, 0]
    w = u @ ix["ww"]["kernel"]
    k = jnp.repeat(k, n_head // n_kv_head, axis=1)
    v = jnp.repeat(v, n_head // n_kv_head, axis=1)
    out, sets = [], []
    for a in range(0, t, QUERY_BLOCK):
        at = jnp.arange(a, min(a + QUERY_BLOCK, t))
        dots = jnp.einsum("qjd,sd->qjs", qi[at], ki)
        index = (jnp.maximum(dots, 0.0) * w[at][:, :, None]).sum(1)
        chosen = chosen_positions(index, at, topk, select)
        scores = jnp.einsum("qhd,khd->hqk", q[at], k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v).reshape(
            len(at), n_head * d))
        sets.append(chosen)
    return (x + jnp.concatenate(out) @ lp["wo"]["kernel"],
            jnp.concatenate(sets))


def hidden(params, tokens, settings: dict, *, choices=None, select="top",
           chosen=False):
    """tokens (B, T) -> (final-norm states (B, T, E), differs, margin (layers,
    B x T)) and, with ``chosen``, every layer's chosen positions (layers, B,
    T, T) bool.  ``settings``: the config.json keys num_attention_heads,
    num_key_value_heads, head_dim, num_experts_per_tok, rms_norm_eps,
    rope_theta, and the ``sa_config`` group.  ``choices`` (layers, B x T,
    K): a program's chosen expert ids in the tokens' row-major order."""
    eps, k = float(settings["rms_norm_eps"]), settings["num_experts_per_tok"]
    sa = settings["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer's key is one head")
    attn = partial(_attention, n_head=settings["num_attention_heads"],
                   n_kv_head=settings["num_key_value_heads"],
                   head_dim=settings["head_dim"], eps=eps,
                   theta=float(settings["rope_theta"]),
                   index_heads=sa["indexer_num_heads"],
                   index_dim=sa["indexer_head_dim"], topk=sa["topk"],
                   select=select)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    x = _f32(params["wte"][tokens])
    blocks = params["blocks"]
    n_layer = blocks["attn_norm"]["scale"].shape[0]
    attn_keys = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                 "index")
    if choices is None:
        choices = jnp.full((n_layer, b * t, k), -1, jnp.int32)
    choices = jnp.asarray(choices, jnp.int32)
    if choices.shape != (n_layer, b * t, k):
        raise ValueError(f"choices of shape {choices.shape} for {n_layer} "
                         f"routed layers, {b * t} tokens and {k} a token")
    differs, margins, sets = [], [], []
    for layer in range(n_layer):
        lp = jax.tree_util.tree_map(lambda a: a[layer], blocks)
        alp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in attn_keys})
        rows = [attn(x[i], alp) for i in range(b)]
        x = jnp.stack([row[0] for row in rows])
        if chosen:
            sets.append(np.stack([np.asarray(row[1]) for row in rows]))
        y, one, two = _moe(x.reshape(b * t, -1), lp, k=k, eps=eps,
                           chosen=choices[layer])
        x = y.reshape(b, t, -1)
        differs.append(one)
        margins.append(two)
    x = _rms_norm(x, _f32(params["norm_f"]["scale"]), eps)
    out = (x, jnp.stack(differs), jnp.stack(margins))
    return out + (np.stack(sets),) if chosen else out


def logits(params, tokens, settings: dict, choices=None, **variant):
    """tokens (B, T) int -> logits (B, T, V) float32, on the host; under a
    program's ``choices`` (layers, B x T, K) -> (logits, audit)."""
    with jax.default_matmul_precision("highest"):
        x, differs, margin = hidden(params, tokens, settings,
                                    choices=choices, **variant)
        head = params["lm_head"]["kernel"]
        cols = np.linspace(0, head.shape[1], HEAD_COLS + 1).astype(int)
        out = np.concatenate([np.concatenate(
            [np.asarray(x[:, at:at + HEAD_ROWS] @ _f32(head[:, lo:hi]))
             for at in range(0, x.shape[1], HEAD_ROWS)], axis=1)
            for lo, hi in zip(cols, cols[1:])], axis=2)
    if choices is None:
        return out
    return out, {"decisions": int(differs.size),
                 "differing": int(differs.sum()),
                 "worst_margin": float(margin.max())}
