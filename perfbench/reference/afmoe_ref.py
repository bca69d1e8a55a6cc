"""AFMoE (Arcee Trinity Large; ``model_type`` ``afmoe``) in plain float32
jax.numpy: the full forward over whole sequences, no kernel, no sort, no
cache, no chunk, no ring, no batching.  ``perfbench/TRINITY.md`` has the
equations with their sources; in short, with x the residual stream of one
sequence (T, E) and four RMSNorms N1..N4 a layer::

    x_0 = wte[tokens] * sqrt(E)                         (mup_enabled)
    x   = x + N2(W_o ((softmax(q k^T / sqrt(D) + mask) v) * sigmoid(W_g u)))
          u = N1(x); q, k, v = W_q u, W_k u, W_v u as H / KV / KV heads of
          D; RMSNorm over each head's D dimensions of q and of k (a learned
          scale of D each); in a SLIDING layer rotate-half RoPE (pairs (d,
          d + D/2)) at theta over all D and a mask that lets query t see
          the keys t - window + 1 .. t; in a FULL layer no RoPE and every
          key up to t; a KV head serves H / KV query heads; by blocks of
          queries
    x   = x + N4(F(N3(x)))
          dense layer   F(h) = W_2 (silu(W_1 h) * W_3 h)
          routed layer  s = sigmoid(W_r h), float32, over ALL the router's
                        experts; the k chosen are the top of s +
                        expert_bias; w_e = route_scale * s_e / (sum of the
                        chosen s + 1e-20); F(h) = SwiGLU_shared(h) + sum
                        over the chosen experts THAT ARE HELD of w_e
                        SwiGLU_e(h): every held expert is applied to every
                        token and masked by the choice, in blocks of experts
    logits = W_head . RMSNorm_final(x_L)               untied, no bias

The share.  The tree holds ``held`` of the router's experts a routed
layer, from ``first_held`` on (``settings``): one chip's share of a layer
that 8 chips hold by its experts.  A chosen expert that is absent adds
nothing, here as in the program; its weight still counts in the sum that
normalises the chosen weights (the divisor is over the k chosen, wherever
they lie).  The eight shares' routed parts and the shared expert once sum
to the uncut layer (``tests/test_afmoe.py``).

It reads the program's parameter tree (``layers``: one tree a layer;
``wte``, ``ln_f``, ``lm_head``) and nothing else of the program: it
imports nothing from ``ray_tpu``.  A bf16 tree is widened a layer at a
time, the experts a block at a time and the head in slices of the
vocabulary, so that the reference fits beside the engine it checks.  Every
entry point sets ``jax.default_matmul_precision("highest")``.

Under a program's choice of experts (``logits(..., choices=ids)``:
``perfbench/README.md``, a routed family; ``olmoe_ref.py`` says why).  With
``choices`` (routed layers, tokens, k) every routed layer still computes
its own selection scores ``s + expert_bias`` and its own top-k set R, meets
the program's set P, and goes on UNDER P: the experts of P weighed by the
reference's own sigmoids of them under the rule above.  A decision's margin
is ``(s + bias)_(k) - min over e in P of (s + bias)_e``; ``audit`` counts
the ``decisions`` (routed layers x tokens), those ``differing`` (P is not R
as a set) and holds the ``worst_margin``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 4        # experts applied at once: (T, 4, width) float32
QUERY_BLOCK = 256       # queries attended at once: (H, 256, T) float32
HEAD_SLICE = 8192       # columns of the head widened at a time
WEIGHT_EPS = 1e-20      # the family's, in the chosen weights' divisor
SLIDING, FULL = "sliding_attention", "full_attention"


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


def _rope(x, theta: float):
    """x (T, H, D): position t rotates pair (d, d + D/2) by t theta^(-2d/D)."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_head", "n_kv_head", "eps", "theta",
                                   "window"))
def _attention(x, lp, *, n_head, n_kv_head, eps, theta, window):
    """One sequence: x (T, E) float32 -> x + N2(W_o (attention * gate));
    ``window`` None: a full layer (no RoPE, every key up to the query)."""
    t = x.shape[0]
    u = _rms_norm(x, lp["norm1"]["scale"], eps)
    q = (u @ lp["wq"]["kernel"]).reshape(t, n_head, -1)
    k = (u @ lp["wk"]["kernel"]).reshape(t, n_kv_head, -1)
    v = (u @ lp["wv"]["kernel"]).reshape(t, n_kv_head, -1)
    gate = _sigmoid(u @ lp["wg"]["kernel"])
    d = q.shape[-1]
    q = _rms_norm(q, lp["q_norm"]["scale"], eps)
    k = _rms_norm(k, lp["k_norm"]["scale"], eps)
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv_head, axis=1)
    v = jnp.repeat(v, n_head // n_kv_head, axis=1)
    keys = jnp.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):                 # blocks of queries
        rows = jnp.arange(at, min(at + QUERY_BLOCK, t))
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(d)
        seen = keys <= rows[:, None]
        if window is not None:
            seen &= keys > rows[:, None] - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v))
    a = jnp.concatenate(out).reshape(t, n_head * d) * gate
    return x + _rms_norm(a @ lp["wo"]["kernel"], lp["norm2"]["scale"], eps)


def _swiglu(n, ws):
    return (_silu(n @ ws["w1"]) * (n @ ws["w3"])) @ ws["w2"]


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    n = _rms_norm(x, lp["norm3"]["scale"], eps)
    return x + _rms_norm(_swiglu(n, lp["mlp"]), lp["norm4"]["scale"], eps)


@partial(jax.jit, static_argnames=("k", "eps", "scale"))
def _route(x, ffn_scale, w_router, bias, chosen_ids, *, k, eps, scale):
    """x (T, E), a choice of experts (T, K) or None for the reference's own
    -> (n, gates (T, X) over ALL the router's experts: the chosen ones'
    weights and zero elsewhere; per token whether the choice differs from
    the reference's own top-k set, and the margin (s + bias)_(k) - min (s +
    bias)[chosen])."""
    num_experts = w_router.shape[-1]
    n = _rms_norm(x, ffn_scale, eps)
    s = _sigmoid(n @ w_router)
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


@partial(jax.jit, static_argnames=("eps",))
def _close_ffn(x, n, routed, shared, scale4, *, eps):
    return x + _rms_norm(routed + _swiglu(n, shared), scale4, eps)


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


def _routed_ffn(x, lp, experts, *, k, eps, scale, first_held, chosen):
    """x (T, E) -> (x + N4(shared + held experts), differs (T,), margin)."""
    n, gates, differs, margin = _route(
        x, lp["norm3"]["scale"], lp["router"]["kernel"], lp["expert_bias"],
        chosen, k=k, eps=eps, scale=scale)
    y = _close_ffn(x, n, routed_part(n, gates, experts, first_held),
                   lp["shared"], lp["norm4"]["scale"], eps=eps)
    return y, differs, margin


def hidden(params, tokens, settings: dict, choices=None):
    """tokens (B, T) -> (final-norm states (B, T, E), differs, margin), the
    two last (routed layers, B x T).  ``settings``: ``layer_types`` (the
    layers HELD, in order), ``num_dense_layers``, ``num_attention_heads``,
    ``num_key_value_heads``, ``num_experts_per_tok``, ``rms_norm_eps``,
    ``rope_theta``, ``route_scale``, ``sliding_window``, ``first_held``.

    ``choices`` (routed layers, B x T, K): a program's chosen expert ids
    for every token in the tokens' row-major order."""
    eps, k = float(settings["rms_norm_eps"]), settings["num_experts_per_tok"]
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    kinds = list(settings["layer_types"])
    n_dense = settings["num_dense_layers"]
    n_routed = len(kinds) - n_dense
    if choices is not None:
        choices = jnp.asarray(choices, jnp.int32)
        if choices.shape[:2] != (n_routed, b * t):
            raise ValueError(f"choices of shape {choices.shape} for "
                             f"{n_routed} routed layers and {b * t} tokens")
        choices = choices.reshape(n_routed, b, t, -1)
    width = params["wte"].shape[1]
    x = _f32(params["wte"][tokens]) * math.sqrt(width)
    differs, margins = [], []
    for i, kind in enumerate(kinds):
        held = params["layers"][f"l{i:02d}"]
        lp = _widened({n: v for n, v in held.items() if n != "experts"})
        attn = partial(
            _attention, n_head=settings["num_attention_heads"],
            n_kv_head=settings["num_key_value_heads"], eps=eps,
            theta=float(settings["rope_theta"]),
            window=settings["sliding_window"] if kind == SLIDING else None)
        x = jnp.stack([attn(x[j], lp) for j in range(b)])
        if i < n_dense:
            x = jnp.stack([_dense_ffn(x[j], lp, eps=eps) for j in range(b)])
            continue
        outs = [_routed_ffn(
            x[j], lp, held["experts"], k=k, eps=eps,
            scale=float(settings["route_scale"]),
            first_held=settings["first_held"],
            chosen=None if choices is None else choices[i - n_dense, j])
            for j in range(b)]
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
    selection scores."""
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
