"""Nemotron-3-Nano-30B-A3B's decoder (``model_type`` ``nemotron_h``;
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``, after the
release's ``modeling_nemotron_h.py``; arXiv:2504.03624) in plain float32
jax.numpy: forward pass and training loss of one chip's share; gradients
through ``jax.grad``.

No kernel, no chunked form, no sort, no cache, no sharding.  Layer ``i`` is
what ``hybrid_override_pattern[i]`` says: ``M`` Mamba-2, ``E`` experts, ``*``
attention; every layer is ``x <- x + mixer(N(x))`` with ``N(x) = x /
sqrt(mean x^2 + eps) * g``::

    M   [z | xBC | dt] = W_in u           H heads of P, G groups of N columns
        xBC = silu(conv(xBC) + b)         depthwise, causal, K taps
        [x | B | C] = xBC                 head h reads group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        state S_h (P, N), zero at the start, TOKEN BY TOKEN:
            S <- exp(dt_t A) S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t
        m = W_out (N over each group of H P / G channels of (y silu(z)) * w)
    *   q = W_q u;  k, v = W_k u, W_v u;  NO rotary embedding
        m = W_o softmax(q k^T / sqrt(D), causal) v     KV head h // (H / KV)
    E   s = sigmoid(W_r u) over ALL routed experts; chosen = the k largest
        of s + b;  w_e = scale s_e / (sum of the chosen s + 1e-20)
        m = W_down,sh relu(W_up,sh u)^2
            + sum over chosen e THAT ARE HELD of w_e W_down,e relu(W_up,e u)^2
    logits = W_head N(x_L) over the vocabulary rows held
    loss = mean next-token cross entropy; no auxiliary term

**The share is data.**  ``settings["held_expert_ids"]`` lists the routed
experts whose matrices the tree holds, in the tree's order; the router
keeps its full width, a token's weights are normalised over all k it
chose, and what an absent expert would have added is left out (another
chip's part).  The vocabulary is the rows of ``wte`` / columns of
``lm_head`` the tree has.

Departures from the release, each at its line below and under the
configuration's ``assumed``: the recurrence token by token where the
release's ``torch_forward`` and kernels run chunks of ``chunk_size`` (the
same function); no clamp on ``dt`` (``time_step_limit`` is absent from the
config: (0, inf)); the router's logits from float32 operands, as the
release computes them.

It reads the program's parameter tree (``mamba_blocks``, ``expert_blocks``
and ``attn_blocks``, each on a leading layer axis in the order of its
kind, the experts on an expert axis behind it) and nothing else of the
program.  A layer's leaves are cast to float32 as they are used, the
experts one at a time and every held expert applied to every token, masked
by the reference's own choice; attention and the recurrence run one
sequence at a time, attention a block of queries at a time.
``jax.checkpoint`` round a layer, an expert, a block of queries and a
segment of the recurrence changes no value: it is what lets a gradient fit.
Every entry point sets ``jax.default_matmul_precision("highest")``.

``variant`` names ONE deliberately wrong convention (``VARIANTS``): the
tests use them to show that each such mistake in the program would be
caught.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512           # queries scored at once: (H, 512, T) float32
SEGMENT = 256           # positions of the recurrence a checkpoint
KINDS = {"M": "mamba", "E": "expert", "*": "attn"}
VARIANTS = (
    "state_not_carried",    # the state starts at zero at every chunk's edge
    "norm_then_gate",       # norm(y) silu(z) for norm(y silu(z))
    "norm_over_all",        # one RMS over all H P channels, not a group's
    "no_conv_bias",         # the conv without its bias
    "no_skip",              # y without D x
    "rotary",               # rotate-half RoPE on q and k
    "relu_not_squared",     # relu(up) for relu(up)^2
    "bias_weighs",          # the weights from s + b, not from s
    "no_scale",             # the weights without routed_scaling_factor
)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


# ------------------------------------------------------------------ Mamba-2
def recurrence(x, dt, a, b, c, *, reset_every: int = 0):
    """One sequence, token by token: x (T, H, P), dt (T, H) after the
    softplus, a (H,) negative, b, c (T, H, N) -> (y (T, H, P) without the
    skip, the last state (H, P, N)).  ``reset_every`` > 0 (a wrong
    convention) zeroes the state before every such position."""
    t, h, p = x.shape
    keep = jnp.ones((t,), jnp.float32)
    if reset_every:
        keep = (jnp.arange(t) % reset_every != 0).astype(jnp.float32)

    def step(s, xs):
        x_t, dt_t, b_t, c_t, keep_t = xs
        s = s * (keep_t * jnp.exp(dt_t * a))[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    seg = SEGMENT if t % SEGMENT == 0 else t
    xs = jax.tree_util.tree_map(
        lambda v: v.reshape(t // seg, seg, *v.shape[1:]),
        (x, dt, b, c, keep))
    s, y = jax.lax.scan(jax.checkpoint(partial(jax.lax.scan, step)),
                        jnp.zeros((h, p, b.shape[-1]), jnp.float32), xs)
    return y.reshape(t, h, p), s


@partial(jax.jit, static_argnames=("heads", "head_dim", "groups", "state",
                                   "eps", "chunk", "variant"))
def mamba_mixer(u, lp, *, heads, head_dim, groups, state, eps, chunk,
                variant=""):
    """One sequence: normed u (T, E) float32 -> (W_out . mixer (T, E), the
    recurrence's last state (H, P, N))."""
    t = u.shape[0]
    d, gn = heads * head_dim, groups * state
    proj = u @ lp["in_proj"]["kernel"]          # [z | x | B | C | dt]
    z, xbc, dt = proj[:, :d], proj[:, d:2 * d + 2 * gn], proj[:, 2 * d + 2 * gn:]
    w = lp["conv"]["kernel"]                                     # (taps, C)
    taps = w.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = sum(w[i] * padded[i:i + t] for i in range(taps))
    if variant != "no_conv_bias":
        xbc = xbc + lp["conv"]["bias"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d].reshape(t, heads, head_dim)
    b = xbc[:, d:d + gn].reshape(t, groups, state)
    c = xbc[:, d + gn:].reshape(t, groups, state)
    # no clamp on dt: the config has no time_step_limit (the release's
    # default (0, inf))
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    # token by token, where the release runs chunks of chunk_size
    y, last = recurrence(
        x, dt, -jnp.exp(lp["A_log"]), jnp.repeat(b, heads // groups, axis=1),
        jnp.repeat(c, heads // groups, axis=1),
        reset_every=chunk if variant == "state_not_carried" else 0)
    if variant != "no_skip":
        y = y + lp["D"][:, None] * x
    gate = jax.nn.silu(z)
    g = y.reshape(t, d)
    if variant != "norm_then_gate":
        g = g * gate
    per = 1 if variant == "norm_over_all" else groups
    g = g.reshape(t, per, d // per)
    g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
    g = g.reshape(t, d) * lp["ssm_norm"]["scale"]
    if variant == "norm_then_gate":
        g = g * gate
    return g @ lp["out_proj"]["kernel"], last


# ---------------------------------------------------------------- attention
def _rope(x, theta: float = 10000.0):
    """The rotary embedding the family does NOT apply (variant ``rotary``):
    x (T, H, D), pairs (i, i + D / 2)."""
    t, half = x.shape[0], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_head", "n_kv", "d", "variant"))
def attention(u, lp, *, n_head, n_kv, d, variant=""):
    """One sequence: normed u (T, E) float32 -> W_o . attention; q and k
    as projected: the release's attention builds no rotary embedding."""
    t = u.shape[0]
    q = (u @ lp["wq"]["kernel"]).reshape(t, n_head, d)
    k = (u @ lp["wk"]["kernel"]).reshape(t, n_kv, d)
    v = (u @ lp["wv"]["kernel"]).reshape(t, n_kv, d)
    if variant == "rotary":
        q, k = _rope(q), _rope(k)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, n_head * d)
    return o @ lp["wo"]["kernel"]


# ------------------------------------------------------------------ experts
def _two_matrix(n, w_up, w_down, gate, *, squared):
    """``gate`` (N, 1) times the two-matrix expert's output; the matrices
    are cast here, inside the checkpoint, so that a gradient keeps them as
    stored.  No gate matrix: the family's expert has none."""
    up = jax.nn.relu(n @ _f32(w_up))
    return gate * ((up * up if squared else up) @ _f32(w_down))


_EXPERT = {squared: jax.jit(jax.checkpoint(partial(_two_matrix,
                                                   squared=squared)))
           for squared in (True, False)}


@partial(jax.jit, static_argnames=("k", "scale", "variant"))
def _route(n, w_router, bias, *, k, scale, variant):
    """n (N, E) -> (gates (N, X): the weight of each chosen expert, zeros
    off the k chosen; the selection scores s + b (N, X))."""
    s = jax.nn.sigmoid(n @ w_router)        # float32 operands: the release's
    select = s + bias                       # n_group 1: no group limit
    _, top = jax.lax.top_k(select, k)
    chosen = jax.nn.one_hot(top, s.shape[-1], dtype=jnp.float32).sum(1)
    gates = (select if variant == "bias_weighs" else s) * chosen
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * (1.0 if variant == "no_scale" else scale), select


def moe(n, lp, settings: dict, variant=""):
    """Normed n (N, E) float32 -> the shared expert plus the held routed
    experts' part, (N, E)."""
    router = lp["router"]
    gates, _ = _route(n, _f32(router["kernel"]), _f32(router["select_bias"]),
                      k=settings["num_experts_per_tok"],
                      scale=float(settings["routed_scaling_factor"]),
                      variant=variant)
    expert = _EXPERT[variant != "relu_not_squared"]
    sh, ex = lp["shared"], lp["experts"]
    y = expert(n, sh["w_up"]["kernel"], sh["w_down"]["kernel"],
               jnp.ones((1, 1), jnp.float32))
    held = settings["held_expert_ids"]
    assert len(held) == ex["w_up"].shape[0], (len(held), ex["w_up"].shape)
    for i, e in enumerate(held):             # every held expert, every token
        y = y + expert(n, ex["w_up"][i], ex["w_down"][i], gates[:, e:e + 1])
    return y


# -------------------------------------------------------------------- model
MAMBA_KEYS = ("in_proj", "conv", "A_log", "D", "dt_bias", "ssm_norm",
              "out_proj")
ATTN_KEYS = ("wq", "wk", "wv", "wo")


def mixer(u, lp, kind: str, settings: dict, variant=""):
    """Normed u (B, T, E) float32 through a layer's mixer -> (B, T, E)."""
    if kind == "E":
        b, t, e = u.shape
        return moe(u.reshape(b * t, e), lp, settings, variant).reshape(b, t, e)
    if kind == "M":
        own = jax.tree_util.tree_map(_f32, {w: lp[w] for w in MAMBA_KEYS})
        fn = partial(mamba_mixer, heads=settings["mamba_num_heads"],
                     head_dim=settings["mamba_head_dim"],
                     groups=settings["n_groups"],
                     state=settings["ssm_state_size"],
                     eps=float(settings["layer_norm_epsilon"]),
                     chunk=settings["chunk_size"], variant=variant)
        return jnp.stack([fn(u[i], own)[0] for i in range(u.shape[0])])
    own = jax.tree_util.tree_map(_f32, {w: lp[w] for w in ATTN_KEYS})
    fn = partial(attention, n_head=settings["num_attention_heads"],
                 n_kv=settings["num_key_value_heads"], d=settings["head_dim"],
                 variant=variant)
    return jnp.stack([fn(u[i], own) for i in range(u.shape[0])])


def layer(x, lp, kind: str, settings: dict, variant=""):
    """One layer on x (B, T, E) float32 with its (unstacked) leaves."""
    u = _rms(x, _f32(lp["norm"]["scale"]),
             float(settings["layer_norm_epsilon"]))
    return x + mixer(u, lp, kind, settings, variant)


def layers_of(params, settings: dict):
    """[(kind, the layer's unstacked leaves)] in the published order."""
    seen = {kind: 0 for kind in KINDS}
    out = []
    for kind in settings["hybrid_override_pattern"]:
        j = seen[kind]
        seen[kind] += 1
        out.append((kind, jax.tree_util.tree_map(
            lambda a: a[j], params[f"{KINDS[kind]}_blocks"])))
    return out


def hidden_states(params, tokens, settings: dict, variant=""):
    """tokens (B, T) -> [the residual stream after each layer (B, T, E)]
    and the final-norm states."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _f32(params["wte"])[tokens]
    after = []
    for kind, lp in layers_of(params, settings):
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, kind, settings, variant)
        )(x, lp)
        after.append(x)
    return after, _rms(x, _f32(params["norm_f"]["scale"]),
                       float(settings["layer_norm_epsilon"]))


def logits(params, tokens, settings: dict, variant=""):
    """tokens (B, T) int -> logits (B, T, rows held) float32."""
    with jax.default_matmul_precision("highest"):
        _, x = hidden_states(params, tokens, settings, variant)
        return x @ _f32(params["lm_head"]["kernel"])


def loss(params, inputs, targets, settings: dict, variant=""):
    """The training loss, a float32 scalar."""
    with jax.default_matmul_precision("highest"):
        _, x = hidden_states(params, inputs, settings, variant)
        head = _f32(params["lm_head"]["kernel"])
        targets = jnp.asarray(targets, jnp.int32)
        total = 0.0
        for i in range(x.shape[0]):          # one sequence's logits at a time
            logp = jax.nn.log_softmax(x[i] @ head, axis=-1)
            total = total - jnp.take_along_axis(
                logp, targets[i][:, None], -1).sum()
        return total / targets.size
