"""GPT-2 as published (Radford et al. 2019), in plain float32 jax.numpy.

No kernels, no cache, no batching tricks, no remat, no sharding: token and
position embeddings, then per layer LayerNorm -> causal multi-head
attention -> residual, LayerNorm -> 4x MLP with the tanh GELU -> residual,
a final LayerNorm, and the output head tied to the token embedding.  It
reads the program's parameter tree (block leaves stacked on a leading
layer axis; the fused qkv kernel is (E, 3, E)) and nothing else of the
program.  One layer at a time, each cast to float32 as it is used, so that
a bf16 1.5B-parameter tree needs no second full copy.

On a TPU a float32 matmul runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set; every entry point
here sets it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_EPS = 1e-5


def _layer_norm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _EPS) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, n_head: int):
    """x (B, T, E) float32, lp one layer's parameters in float32."""
    b, t, e = x.shape
    d = e // n_head
    h = _layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"])
    qkv = jnp.einsum("bte,eck->btck", h, lp["attn_qkv"]["kernel"]) \
        + lp["attn_qkv"]["bias"]
    q, k, v = (qkv[:, :, i, :].reshape(b, t, n_head, d) for i in range(3))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, e)
    x = x + a @ lp["attn_out"]["kernel"] + lp["attn_out"]["bias"]
    h = _layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"])
    h = _gelu_tanh(h @ lp["mlp_in"]["kernel"] + lp["mlp_in"]["bias"])
    return x + h @ lp["mlp_out"]["kernel"] + lp["mlp_out"]["bias"]


_block_jit = jax.jit(_block, static_argnames="n_head")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def logits(params, tokens, n_head: int):
    """tokens (B, T) int -> logits (B, T, V) float32."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        wte = jnp.asarray(params["wte"], jnp.float32)
        wpe = jnp.asarray(params["wpe"], jnp.float32)
        x = wte[tokens] + wpe[jnp.arange(tokens.shape[1])]
        n_layer = params["blocks"]["ln_1"]["scale"].shape[0]
        for layer in range(n_layer):
            lp = _f32(jax.tree_util.tree_map(lambda a: a[layer],
                                             params["blocks"]))
            x = _block_jit(x, lp, n_head=n_head)
        x = _layer_norm(x, jnp.asarray(params["ln_f"]["scale"], jnp.float32),
                        jnp.asarray(params["ln_f"]["bias"], jnp.float32))
        return jnp.einsum("bte,ve->btv", x, wte)


def loss(params, inputs, targets, n_head: int):
    """Mean next-token cross entropy of each sequence: (B,) float32."""
    lg = logits(params, inputs, n_head)
    logp = jax.nn.log_softmax(lg, axis=-1)
    targets = jnp.asarray(targets, jnp.int32)
    picked = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return -picked.mean(-1)
