"""Falcon-H1 as published (TII 2025; ``transformers``'
``modeling_falcon_h1.py``), in plain float32 jax.numpy: the full forward
over whole sequences, no cache, no bucket, no chunk, no kernel.

Per layer, on the same normed input ``u = RMSNorm(x)``:

* the Mamba-2 mixer: ``p = (W_in (ssm_in_multiplier * u)) * mup`` with
  ``mup`` the five ``ssm_multipliers`` over the segments ``[z | x | B | C
  | dt]``; a depthwise causal conv (with bias) over ``[x | B | C]`` as
  shifted sums, then silu; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the recurrence as the literal scan over tokens, ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``
  (head h reads group ``h // (heads / groups)``); RMSNorm over each group
  of ``y * silu(z)`` (``mamba_norm_before_gate`` false); ``W_out``;
* grouped-query attention on ``attention_in_multiplier * u``, the keys
  times ``key_multiplier``, RoPE over the whole head (rotate-half: pairs
  ``(d, d + D/2)``), a dense masked softmax;

then ``h = x + ssm_out_multiplier * m + attention_out_multiplier * a`` and
``y = h + mlp_multipliers[1] * W_down(silu(mlp_multipliers[0] * W_gate n)
* W_up n)``, ``n = RMSNorm(h)``.  The embedding is multiplied by
``embedding_multiplier``, the logits by ``lm_head_multiplier``; the head
is untied; no bias but the conv's.

Departures from the published code, each also under ``assumed`` in the
configuration's file: weights are random (the program's ``init_params``);
``W_in``'s segments are laid out ``[z | x | B | C | dt]`` as the published
``in_proj`` is split; the state is float32.

It reads the program's parameter tree (block leaves stacked on a leading
layer axis) and nothing else of the program: it imports nothing from
``ray_tpu``.  A bf16 tree is widened a layer at a time, the head in slices
of the vocabulary, so that the reference fits beside the engine it checks
(one layer of the 34B model is 1.72 GB in float32).

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; every entry point
here sets it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HEAD_SLICE = 32768          # columns of the head widened at a time


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """x (B, T, H, D): rotate pairs (d, d + D/2) by position * theta^(-d/(D/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mixer(u, lp, s):
    b, t, _ = u.shape
    heads, p, n, g = (s["mamba_n_heads"], s["mamba_d_head"],
                      s["mamba_d_state"], s["mamba_n_groups"])
    d_ssm, k_w = s["mamba_d_ssm"], s["mamba_d_conv"]
    gn = g * n
    widths = (d_ssm, d_ssm, gn, gn, heads)
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32)
                           for w, m in zip(widths, s["ssm_multipliers"])])
    proj = ((s["ssm_in_multiplier"] * u) @ lp["ssm_in"]["kernel"]) * mup
    z, xbc, dt = (proj[..., :d_ssm], proj[..., d_ssm:2 * d_ssm + 2 * gn],
                  proj[..., 2 * d_ssm + 2 * gn:])
    # the conv as shifted sums: w[K-1] takes the current token
    w = lp["conv"]["kernel"]
    conv = jnp.zeros_like(xbc) + lp["conv"]["bias"]
    for back in range(k_w):
        shifted = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :t]
        conv = conv + w[k_w - 1 - back] * shifted
    xbc = _silu(conv)
    x = xbc[..., :d_ssm].reshape(b, t, heads, p)
    bm = xbc[..., d_ssm:d_ssm + gn].reshape(b, t, g, n)
    cm = xbc[..., d_ssm + gn:].reshape(b, t, g, n)
    bm = jnp.repeat(bm, heads // g, axis=2)            # (B, T, H, N)
    cm = jnp.repeat(cm, heads // g, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])           # (B, T, H)
    a = -jnp.exp(lp["A_log"])

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs                       # (B,H,P) (B,H,N) .. (B,H)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        y_t = (state * c_t[:, :, None, :]).sum(-1) + lp["D"][:, None] * x_t
        return state, y_t

    state = jnp.zeros((b, heads, p, n), jnp.float32)
    _, y = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, d_ssm) * _silu(z)
    y = y.reshape(b, t, g, d_ssm // g)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + s["rms_norm_eps"])
    y = y.reshape(b, t, d_ssm) * lp["ssm_norm"]["scale"]
    return y @ lp["ssm_out"]["kernel"]


def _attention(u, lp, s):
    b, t, _ = u.shape
    h, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    a_in = s["attention_in_multiplier"] * u
    q = (a_in @ lp["wq"]["kernel"]).reshape(b, t, h, d)
    k = (s["key_multiplier"] * (a_in @ lp["wk"]["kernel"])).reshape(b, t, kv, d)
    v = (a_in @ lp["wv"]["kernel"]).reshape(b, t, kv, d)
    q, k = _rope(q, float(s["rope_theta"])), _rope(k, float(s["rope_theta"]))
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * d)
    return out @ lp["wo"]["kernel"]


def _block(x, lp, s):
    """x (B, T, E) float32, lp one layer's parameters in float32."""
    eps = s["rms_norm_eps"]
    u = _rms_norm(x, lp["norm"]["scale"], eps)
    h = x + s["ssm_out_multiplier"] * _mixer(u, lp, s) \
        + s["attention_out_multiplier"] * _attention(u, lp, s)
    n = _rms_norm(h, lp["mlp_norm"]["scale"], eps)
    gate_m, down_m = s["mlp_multipliers"]
    gate = _silu(gate_m * (n @ lp["w_gate"]["kernel"]))
    return h + down_m * ((gate * (n @ lp["w_up"]["kernel"]))
                         @ lp["w_down"]["kernel"])


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


class _Sizes(dict):
    """The sizes as a jit-static argument (hashable by content)."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


_block_jit = jax.jit(_block, static_argnames="s")


@partial(jax.jit, static_argnames="mult")
def _head_slice(x, w, mult):
    return mult * (x @ jnp.asarray(w, jnp.float32))


def logits(params, tokens, sizes: dict):
    """tokens (B, T) int -> logits (B, T, V) float32."""
    s = _Sizes(sizes)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = s["embedding_multiplier"] \
            * jnp.asarray(params["wte"][tokens], jnp.float32)
        for layer in range(s["num_hidden_layers"]):
            lp = _f32(jax.tree_util.tree_map(lambda a: a[layer],
                                             params["blocks"]))
            x = _block_jit(x, lp, s=s)
            del lp      # or two layers' float32 copies are alive at once
        x = _rms_norm(x, jnp.asarray(params["norm_f"]["scale"], jnp.float32),
                      s["rms_norm_eps"])
        head = params["lm_head"]["kernel"]
        return jnp.concatenate([
            _head_slice(x, head[:, at:at + HEAD_SLICE],
                        mult=s["lm_head_multiplier"])
            for at in range(0, head.shape[1], HEAD_SLICE)], axis=-1)
