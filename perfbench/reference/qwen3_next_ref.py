"""Qwen3-Next-80B-A3B's decoder (``model_type`` ``qwen3_next``;
Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``, after ``transformers``'
``modeling_qwen3_next.py``) in plain float32 jax.numpy: forward pass and
training loss of one chip's share; gradients through ``jax.grad``.

No kernel, no chunked form, no sort, no cache, no sharding.  Layer ``i`` is
full attention where ``(i + 1) % full_attention_interval == 0``, else Gated
DeltaNet.  Per layer, with x the residual stream and ``rms0(x) = x
rsqrt(mean x^2 + eps) (1 + w)`` (the zero-centred norm)::

    u = rms0(x)
    Gated DeltaNet (G key heads of dk, H = G R value heads of dv):
        [q | k | v | z] = W_qkvz u;   [b | a] = W_ba u
        [q | k | v] = silu(conv([q | k | v]))     depthwise, causal, 4 taps,
                                                  no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q = q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k = k / sqrt(sum k^2 + 1e-6)
        value head h reads key head h // R; state S_h (dk, dv), zero at
        the start, TOKEN BY TOKEN:
            S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
            o_t = S^T q_t
        y = x + W_out (w . o rsqrt(mean o^2 + eps) . silu(z))   a head's dv;
                                                 the plain norm, w near 1
    full attention (H heads of D, KV key/value heads):
        [q_h | gate_h] = W_q u;  k, v = W_k u, W_v u
        q, k = rms0(q), rms0(k) over D
        RoPE at theta on the first ``rotary`` lanes of a head by halves
        (pairs (i, i + rotary / 2)); the other lanes pass
        a_h = softmax(q_h k^T / sqrt(D), causal) v     KV head h // (H / KV)
        y = x + W_o (a . sigmoid(gate))
    n = rms0(y)
    p = softmax(W_r n) over ALL routed experts; chosen = its top k;
        w_e = p_e / sum of p over the k chosen
    out = y + sigmoid(W_sg n) Shared(n)
            + sum over chosen e THAT ARE HELD of w_e Expert_e(n)
    logits = W_head rms0(x_L) over the vocabulary rows held
    loss = mean next-token cross entropy; no auxiliary term

**The share is data.**  ``settings["held_expert_ids"]`` lists the routed
experts whose matrices the tree holds, in the tree's order; the router
keeps its full width, a token's weights are normalised over all k it
chose, and what an absent expert would have added is left out (another
chip's part).  The vocabulary is the rows of ``wte`` / columns of
``lm_head`` the tree has.

Departures from the published code, each also under the configuration's
``assumed``: ``in_proj_qkvz``'s columns lie [q | k | v | z] and
``in_proj_ba``'s [b | a], heads in order (published: grouped by key head;
a permutation of the columns of random matrices); no multi-token
prediction module (the config has no key for it).

It reads the program's parameter tree (``gdn_blocks`` and ``attn_blocks``,
each on a leading layer axis in the order of its kind, the experts on an
expert axis behind it) and nothing else of the program.  A layer's leaves
are cast to float32 as they are used, the experts one at a time and every
held expert applied to every token, masked by the choice; attention and
the recurrence run one sequence at a time.  ``jax.checkpoint`` round a
layer, an expert, a block of queries and a segment of the recurrence
changes no value: it is what lets a gradient at 8,192 positions fit a
chip.  Every entry point sets ``jax.default_matmul_precision("highest")``.

``variant`` names ONE deliberately wrong convention (``VARIANTS``): the
tests and ``benchmarks/qwen3_next_check.py`` use them to show that each
such mistake in the program would be caught.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512           # queries scored at once: (H, 512, T) float32
SEGMENT = 256           # positions of the recurrence a checkpoint
VARIANTS = (
    "no_decay",             # the state never forgets: exp(g) = 1
    "no_beta",              # beta = 1
    "state_not_carried",    # the state starts at zero at every chunk's edge
    "no_l2norm",            # q and k as the conv gave them
    "gate_before_norm",     # norm(o silu(z)) for norm(o) silu(z)
    "plain_norm",           # w for (1 + w) in the zero-centred norms
    "rope_all_lanes",       # rotary over the whole head
    "no_attn_gate",         # the attention output ungated
    "no_shared_gate",       # the shared expert ungated
    "no_renorm",            # the chosen probabilities as they are
)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms0(x, w, eps, variant=""):
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y * (w if variant == "plain_norm" else 1.0 + w)


# ----------------------------------------------------------- Gated DeltaNet
def recurrence(q, k, v, g, beta, *, reset_every: int = 0):
    """One sequence, token by token: q, k (T, H, dk), v (T, H, dv), g, beta
    (T, H) -> (o (T, H, dv), the last state (H, dk, dv)).  ``reset_every``
    > 0 (a wrong convention) zeroes the state before every such position."""
    t, h, dk = q.shape
    keep = jnp.ones((t,), jnp.float32)
    if reset_every:
        keep = (jnp.arange(t) % reset_every != 0).astype(jnp.float32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t, keep_t = x
        s = s * keep_t * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    seg = SEGMENT if t % SEGMENT == 0 else t
    xs = jax.tree_util.tree_map(
        lambda a: a.reshape(t // seg, seg, *a.shape[1:]),
        (q, k, v, g, beta, keep))
    s, o = jax.lax.scan(jax.checkpoint(partial(jax.lax.scan, step)),
                        jnp.zeros((h, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(t, h, -1), s


@partial(jax.jit, static_argnames=("g_heads", "h_heads", "dk", "dv", "eps",
                                   "chunk", "variant"))
def gdn_mixer(u, lp, *, g_heads, h_heads, dk, dv, eps, chunk, variant=""):
    """One sequence: normed u (T, E) float32 -> (W_out . rule (T, E), the
    rule's last state (H, dk, dv))."""
    t = u.shape[0]
    kw, r = g_heads * dk, h_heads // g_heads
    qkvz = u @ lp["in_proj_qkvz"]["kernel"]
    ba = u @ lp["in_proj_ba"]["kernel"]
    qkv, z = qkvz[:, :2 * kw + h_heads * dv], qkvz[:, 2 * kw + h_heads * dv:]
    w = lp["conv"]["kernel"]                                 # (taps, C)
    taps = w.shape[0]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w[i] * padded[i:i + t] for i in range(taps)))
    q = qkv[:, :kw].reshape(t, g_heads, dk)
    k = qkv[:, kw:2 * kw].reshape(t, g_heads, dk)
    v = qkv[:, 2 * kw:].reshape(t, h_heads, dv)
    beta = jax.nn.sigmoid(ba[:, :h_heads])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, h_heads:]
                                                + lp["dt_bias"])
    if variant != "no_l2norm":
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q = q / math.sqrt(dk)
    if variant == "no_decay":
        g = jnp.zeros_like(g)
    if variant == "no_beta":
        beta = jnp.ones_like(beta)
    o, state = recurrence(
        jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, g, beta,
        reset_every=chunk if variant == "state_not_carried" else 0)
    gate = jax.nn.silu(z.reshape(t, h_heads, dv))
    if variant == "gate_before_norm":
        o = o * gate
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
        * lp["out_norm"]["scale"]
    if variant != "gate_before_norm":
        o = o * gate
    return o.reshape(t, h_heads * dv) @ lp["out_proj"]["kernel"], state


# ---------------------------------------------------------- full attention
def _rope(x, rotary: int, theta: float):
    """x (T, H, D): lanes (i, i + rotary / 2) of the first ``rotary`` turn
    by t . theta^(-2i / rotary); the other lanes pass."""
    t = x.shape[0]
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], -1)


@partial(jax.jit, static_argnames=("n_head", "n_kv", "d", "rotary", "eps",
                                   "theta", "variant"))
def attention(u, lp, *, n_head, n_kv, d, rotary, eps, theta, variant=""):
    """One sequence: normed u (T, E) float32 -> W_o (attention . gate)."""
    t = u.shape[0]
    qg = (u @ lp["wq"]["kernel"]).reshape(t, n_head, 2, d)
    q, gate = qg[:, :, 0], qg[:, :, 1]
    k = (u @ lp["wk"]["kernel"]).reshape(t, n_kv, d)
    v = (u @ lp["wv"]["kernel"]).reshape(t, n_kv, d)
    q = _rms0(q, lp["q_norm"]["scale"], eps, variant)
    k = _rms0(k, lp["k_norm"]["scale"], eps, variant)
    if variant == "rope_all_lanes":
        rotary = d
    q, k = _rope(q, rotary, theta), _rope(k, rotary, theta)
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

    a = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, n_head, d)
    if variant != "no_attn_gate":
        a = a * jax.nn.sigmoid(gate)
    return a.reshape(t, n_head * d) @ lp["wo"]["kernel"]


# ------------------------------------------------------------------ experts
@jax.jit
@jax.checkpoint
def _swiglu(n, w_gate, w_up, w_down, gate=1.0):
    """``gate`` (N, 1) times the expert's output; the matrices are cast
    here, inside the checkpoint, so that a gradient keeps them as stored."""
    w_gate, w_up, w_down = _f32(w_gate), _f32(w_up), _f32(w_down)
    return gate * ((jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down)


@partial(jax.jit, static_argnames=("k", "variant"))
def _route(n, w_router, *, k, variant):
    """n (N, E) -> gates (N, X): the weight of each chosen expert, zeros
    off the k chosen."""
    probs = jax.nn.softmax(n @ w_router, axis=-1)
    _, top = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(top, probs.shape[-1], dtype=jnp.float32).sum(1)
    gates = probs * chosen
    if variant != "no_renorm":
        gates = gates / gates.sum(-1, keepdims=True)
    return gates


def moe(n, lp, settings: dict, variant=""):
    """Normed n (N, E) float32 -> the gated shared expert plus the held
    routed experts' part, (N, E)."""
    gates = _route(n, _f32(lp["router"]["kernel"]),
                   k=settings["num_experts_per_tok"], variant=variant)
    sh, ex = lp["shared"], lp["experts"]
    y = _swiglu(n, *(sh[w]["kernel"] for w in ("w_gate", "w_up", "w_down")))
    if variant != "no_shared_gate":
        y = y * jax.nn.sigmoid(n @ _f32(lp["shared_gate"]["kernel"]))
    held = settings["held_expert_ids"]
    assert len(held) == ex["w_gate"].shape[0], (len(held), ex["w_gate"].shape)
    for i, e in enumerate(held):             # every held expert, every token
        y = y + _swiglu(n, ex["w_gate"][i], ex["w_up"][i], ex["w_down"][i],
                        gates[:, e:e + 1])
    return y


# -------------------------------------------------------------------- model
GDN_KEYS = ("in_proj_qkvz", "in_proj_ba", "conv", "A_log", "dt_bias",
            "out_norm", "out_proj")
ATTN_KEYS = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")


def mixer(u, lp, kind: str, settings: dict, variant=""):
    """Normed u (B, T, E) float32 through the layer's mixer -> ((B, T, E),
    the DeltaNet states (B, H, dk, dv) | None)."""
    eps = float(settings["rms_norm_eps"])
    if kind == "gdn":
        mlp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in GDN_KEYS})
        fn = partial(gdn_mixer, g_heads=settings["linear_num_key_heads"],
                     h_heads=settings["linear_num_value_heads"],
                     dk=settings["linear_key_head_dim"],
                     dv=settings["linear_value_head_dim"], eps=eps,
                     chunk=settings.get("rule_chunk", 64), variant=variant)
        outs = [fn(u[i], mlp) for i in range(u.shape[0])]
        return (jnp.stack([o for o, _ in outs]),
                jnp.stack([s for _, s in outs]))
    mlp = jax.tree_util.tree_map(_f32, {w: lp[w] for w in ATTN_KEYS})
    fn = partial(attention, n_head=settings["num_attention_heads"],
                 n_kv=settings["num_key_value_heads"], d=settings["head_dim"],
                 rotary=int(settings["head_dim"]
                            * settings["partial_rotary_factor"]),
                 eps=eps, theta=float(settings["rope_theta"]),
                 variant=variant)
    return jnp.stack([fn(u[i], mlp) for i in range(u.shape[0])]), None


def layer(x, lp, kind: str, settings: dict, variant=""):
    """One block on x (B, T, E) float32 with its (unstacked) leaves."""
    eps = float(settings["rms_norm_eps"])
    b, t, e = x.shape
    mixed, _ = mixer(_rms0(x, _f32(lp["mixer_norm"]["scale"]), eps, variant),
                     lp, kind, settings, variant)
    y = (x + mixed).reshape(b * t, e)
    n = _rms0(y, _f32(lp["mlp_norm"]["scale"]), eps, variant)
    return (y + moe(n, lp, settings, variant)).reshape(b, t, e)


def layers_of(params, settings: dict):
    """[(kind, the layer's unstacked leaves)] in the published order."""
    interval = settings["full_attention_interval"]
    out = []
    for i in range(settings["num_hidden_layers"]):
        kind = "attn" if (i + 1) % interval == 0 else "gdn"
        j = i // interval if kind == "attn" \
            else i - i // interval
        out.append((kind, jax.tree_util.tree_map(
            lambda a: a[j], params[f"{kind}_blocks"])))
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
    return after, _rms0(x, _f32(params["norm_f"]["scale"]),
                        float(settings["rms_norm_eps"]), variant)


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
