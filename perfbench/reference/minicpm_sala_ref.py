"""MiniCPM-SALA as published (openbmb; ``model_type`` ``minicpm_sala``), in
plain float32 jax.numpy: the full forward over a whole sequence, no kernel,
no cache, no chunk, no page, no carried state between calls.

``x`` is a position's stream.  ``h_0 = scale_emb * wte[token]``; every layer

    h = h + r * Mixer(RMSNorm(h))
    h = h + r * W_down(silu(W_gate u) * W_up u),   u = RMSNorm(h)

``r = scale_depth / sqrt(depth)`` with the PUBLISHED depth (32), also where
fewer layers are held; logits ``= W_head RMSNorm(h) / (hidden_size /
dim_model_base)``.  No bias, the head untied.

    lightning-attn   q, k, v = W_q u, W_k u, W_v u, H heads of D; RMSNorm over
                     each head's D of q and of k; RoPE rotate-half over D at
                     theta; token by token  S_t = lambda_h S_(t-1) + k_t v_t^T,
                     o_t = S_t^T q_t / sqrt(D),  lambda_h = exp(-s_h),
                     s_h = 2^(-8 h / H) for h = 1..H;  o = RMSNorm_head(o) *
                     sigmoid(W_g u);  out = W_o o
    minicpm4         q = W_q u (H heads), k, v = W_k u, W_v u (KV heads, H / KV
                     query heads share one); RMSNorm a head on q and k; no
                     RoPE.  A query at position t, n = t + 1:
                     n <= dense_len: causal softmax over all positions,
                     scale 1 / sqrt(D).  Else for each KV head g:
                       Kc_j = mean(k[stride j : stride j + kernel]) for every
                              window that lies whole in 0..t
                       p_hj = softmax_j(q_h . Kc_j / sqrt(D)), each head of g
                       r_j  = sum_h p_hj
                       s_b  = max r_j over the kernels that overlap block b
                              (positions block b .. block b + block - 1)
                       chosen: the first init_blocks blocks, every block that
                              overlaps positions n - window .. t, then the
                              best-scoring others, topk in all; ties to the
                              lower block
                     o_h = causal softmax of q_h over the chosen blocks'
                     positions;  o = o * sigmoid(W_g u);  out = W_o o

Departures and what the source does not say, each also under ``assumed`` in
the configuration's file: weights are random (the program's
``init_params``); the seven sizes of ``sparse_config`` are MiniCPM4's; forced
blocks count among the ``topk``; the softmax over kernels is exact (InfLLM-v2's
kernel approximates its normaliser); the Lightning slopes are ALiBi's with no
per-layer factor, its output norm is over each head's D, state and decay
float32.

It reads the program's parameter tree (``layers``: one tree a layer, ``l00``,
``l01`` ...; ``wte``, ``ln_f``, ``lm_head``) and nothing else of the program:
it imports nothing from ``ray_tpu``.  A bf16 tree is widened a matrix
at a time, where it is used, and the stream handled in blocks of positions (the feed-forward, the
queries, the head), the logits brought to the host a block at a time, so that
the reference fits beside the engine it checks.  Every entry point sets
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul runs
in lower precision without it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

POSITION_BLOCK = 2048   # positions through the feed-forward and the head
QUERY_BLOCK = 256       # queries attended at once: (H, 256, T) float32
HEAD_SLICE = 16384      # columns of the head widened at a time
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
NEG = -1e30


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _w(lp, name):
    """A layer's matrix, widened where it is used: a bf16 tree is never
    held in float32 a layer at a time."""
    return _f32(lp[name]["kernel"])


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


def _blocks(n: int, size: int):
    return [(a, min(n, a + size)) for a in range(0, n, size)]


# ----------------------------------------------------------------- lightning
@partial(jax.jit, static_argnames=("heads", "eps", "theta"))
def _lightning(u, lp, *, heads, eps, theta):
    """Normed stream (T, E) -> the mixer's output (T, E)."""
    t = u.shape[0]
    q = (u @ _w(lp, "wq")).reshape(t, heads, -1)
    k = (u @ _w(lp, "wk")).reshape(t, heads, -1)
    v = (u @ _w(lp, "wv")).reshape(t, heads, -1)
    d = q.shape[-1]
    q = _rope(_rms_norm(q, lp["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, lp["k_norm"]["scale"], eps), theta)
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                    / heads)
    lam = jnp.exp(-slope)[:, None, None]

    def token(s, qkv):
        q_t, k_t, v_t = qkv                                   # (H, D) each
        s = lam * s + k_t[:, :, None] * v_t[:, None, :]      # S = lam S + k v^T
        return s, jnp.einsum("hkv,hk->hv", s, q_t) / math.sqrt(d)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v))
    o = _rms_norm(o, lp["out_norm"]["scale"], eps)
    gate = _sigmoid(u @ _w(lp, "wg"))
    return (o.reshape(t, heads * d) * gate) @ _w(lp, "wo")


# -------------------------------------------------------------------- sparse
def _kernel_windows(t: int, kernel: int, stride: int):
    """Start of every window of ``kernel`` positions at ``stride`` that
    lies whole in a sequence of ``t``."""
    return np.arange(0, max(0, t - kernel + 1), stride)


@partial(jax.jit, static_argnames=("spec",))
def _chosen_positions(q, kc, positions, *, spec):
    """Which positions each query of a block may read.

    q (Tq, KV, R, D); kc (J, KV, D): every whole window's mean key;
    positions (Tq,).  Returns (KV, Tq, T) bool, T = spec's sequence length."""
    kernel, stride, block, init, window, topk, dense_len, t = spec
    tq, kv, rep, d = q.shape
    n_blocks = -(-t // block)
    key_pos = jnp.arange(t)
    n = positions + 1
    causal = key_pos[None, :] <= positions[:, None]                 # (Tq, T)
    if kc.shape[0] == 0:
        return jnp.broadcast_to(causal, (kv, tq, t))
    starts = jnp.asarray(_kernel_windows(t, kernel, stride))
    whole = starts[None, :] + kernel - 1 <= positions[:, None]      # (Tq, J)
    logits = jnp.einsum("tgrd,jgd->gtrj", q, kc) / math.sqrt(d)
    logits = jnp.where(whole[None, :, None, :], logits, NEG)
    logits = logits - logits.max(-1, keepdims=True)
    p = jnp.where(whole[None, :, None, :], jnp.exp(logits), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    r = p.sum(2)                                                 # (KV, Tq, J)
    # kernel j covers starts_j .. starts_j + kernel - 1; block b covers
    # block b .. block b + block - 1
    b_lo = jnp.arange(n_blocks) * block
    overlap = (starts[:, None] < b_lo[None, :] + block) & \
        (starts[:, None] + kernel > b_lo[None, :])               # (J, NB)
    seen = overlap[None, None] & whole[None, :, :, None]
    score = jnp.where(seen, r[..., None], NEG).max(2)           # (KV, Tq, NB)
    exists = b_lo[None, :] <= positions[:, None]                 # (Tq, NB)
    local = b_lo[None, :] + block > (n - window)[:, None]
    forced = ((jnp.arange(n_blocks) < init)[None, :] | local) & exists
    key = jnp.where(forced[None], jnp.inf, score)
    key = jnp.where(exists[None], key, -jnp.inf)
    # the topk best, ties to the lower block: a stable sort of -key
    order = jnp.argsort(-key, axis=-1, stable=True)[..., :topk]
    taken = jnp.zeros((kv, tq, n_blocks), bool)
    taken = taken.at[jnp.arange(kv)[:, None, None],
                     jnp.arange(tq)[None, :, None], order].set(True)
    taken = taken & exists[None]
    dense = (n <= dense_len)[None, :, None]
    taken = jnp.where(dense, exists[None], taken)
    by_position = jnp.repeat(taken, block, axis=-1)[..., :t]
    return by_position & causal[None]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "kernel",
                                   "stride"))
def _sparse_heads(u, lp, *, heads, kv_heads, eps, kernel, stride):
    """Normed stream (T, E) -> q (T, KV, R, D), k, v (T, KV, D), q and k
    normed a head, and every whole window's mean key (J, KV, D)."""
    t = u.shape[0]
    q = (u @ _w(lp, "wq")).reshape(t, heads, -1)
    k = (u @ _w(lp, "wk")).reshape(t, kv_heads, -1)
    v = (u @ _w(lp, "wv")).reshape(t, kv_heads, -1)
    d = q.shape[-1]
    q = _rms_norm(q, lp["q_norm"]["scale"], eps)
    k = _rms_norm(k, lp["k_norm"]["scale"], eps)
    starts = _kernel_windows(t, kernel, stride)
    kc = k[starts[:, None] + np.arange(kernel)[None, :]].mean(1) \
        if len(starts) else jnp.zeros((0, kv_heads, d), jnp.float32)
    return q.reshape(t, kv_heads, heads // kv_heads, d), k, v, kc


@partial(jax.jit, static_argnames=("spec",))
def _sparse_block(q, k, v, kc, positions, *, spec):
    """A block of queries (Tq, KV, R, D) over the whole sequence's k, v
    (T, KV, D), each over the positions it may read -> (Tq, KV, R, D)."""
    allowed = _chosen_positions(q, kc, positions, spec=spec)
    s = jnp.einsum("tgrd,kgd->gtrk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(allowed[:, :, None, :], s, NEG)
    s = s - s.max(-1, keepdims=True)
    p = jnp.where(allowed[:, :, None, :], jnp.exp(s), 0.0)
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("gtrk,kgd->tgrd", p, v)


@jax.jit
def _sparse_out(o, u, lp):
    gate = _sigmoid(u @ _w(lp, "wg"))
    return (o.reshape(o.shape[0], -1) * gate) @ _w(lp, "wo")


def _sparse(u, lp, *, heads, kv_heads, eps, sparse):
    """Normed stream (T, E) -> the mixer's output (T, E), the queries a
    block at a time."""
    kernel, stride = sparse[:2]
    t = u.shape[0]
    q, k, v, kc = _sparse_heads(u, lp, heads=heads, kv_heads=kv_heads,
                                eps=eps, kernel=kernel, stride=stride)
    o = jnp.concatenate([
        _sparse_block(q[a:b], k, v, kc, jnp.arange(a, b),
                      spec=(*sparse, t))
        for a, b in _blocks(t, QUERY_BLOCK)])
    return _sparse_out(o, u, lp)


# --------------------------------------------------------------------- layer
@partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, *, eps):
    return _rms_norm(x, scale, eps)


@partial(jax.jit, static_argnames=("eps", "r"))
def _ffn(h, lp, *, eps, r):
    u = _rms_norm(h, lp["mlp_norm"]["scale"], eps)
    return h + r * ((_silu(u @ _w(lp, "w_gate"))
                     * (u @ _w(lp, "w_up"))) @ _w(lp, "w_down"))


@partial(jax.jit, static_argnames=("eps", "divisor"))
def _head(x, scale, w, *, eps, divisor):
    return (_rms_norm(x, scale, eps) @ w) / divisor


def _one(params, tokens, sizes):
    """One sequence: tokens (T,) -> logits (T, V) float32, on the host."""
    eps = float(sizes["rms_norm_eps"])
    r = float(sizes["scale_depth"]) / math.sqrt(sizes["depth"])
    sc = sizes["sparse_config"]
    sparse = tuple(int(sc[key]) for key in (
        "kernel_size", "kernel_stride", "block_size", "init_blocks",
        "window_size", "topk", "dense_len"))
    x = _f32(params["wte"][tokens]) * float(sizes["scale_emb"])
    for i, kind in enumerate(sizes["mixer_types"]):
        lp = params["layers"][f"l{i:02d}"]
        u = _normed(x, lp["norm"]["scale"], eps=eps)
        if kind == LIGHTNING:
            m = _lightning(u, lp, heads=int(sizes["lightning_nh"]), eps=eps,
                           theta=float(sizes["rope_theta"]))
        else:
            m = _sparse(u, lp, heads=int(sizes["num_attention_heads"]),
                        kv_heads=int(sizes["num_key_value_heads"]), eps=eps,
                        sparse=sparse)
        x = x + r * m
        x = jnp.concatenate([_ffn(x[a:b], lp, eps=eps, r=r)
                             for a, b in _blocks(x.shape[0], POSITION_BLOCK)])
    scale, w = _f32(params["ln_f"]["scale"]), params["lm_head"]["kernel"]
    divisor = sizes["hidden_size"] / sizes["dim_model_base"]
    out = np.empty((x.shape[0], w.shape[1]), np.float32)
    for lo, hi in _blocks(w.shape[1], HEAD_SLICE):
        part = _f32(w[:, lo:hi])
        for a, b in _blocks(x.shape[0], POSITION_BLOCK):
            out[a:b, lo:hi] = np.asarray(_head(x[a:b], scale, part, eps=eps,
                                               divisor=divisor))
    return out


def logits(params, tokens, sizes):
    """Float32 logits (B, T, V) of ``tokens`` (B, T), on the host."""
    with jax.default_matmul_precision("highest"):
        return np.stack([_one(params, jnp.asarray(row, jnp.int32), sizes)
                         for row in np.asarray(tokens)])
