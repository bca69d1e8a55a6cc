"""Paged decode attention: one query token attending over a block table.

Reference design: PagedAttention (Kwon et al., SOSP '23 / vLLM) — the KV
cache of a running sequence is not one contiguous region but a list of
fixed-size *blocks* owned by an allocator; attention reads through a
per-sequence **block table** (block indices into a shared pool).  The
engine (``ray_tpu/serve/llm``) keeps the pool on the device, one array
that its programs take donated and hand back; replicas exchange blocks
by explicit copies over the data plane.

This module is the math: a jit-friendly gather-then-attend decode kernel
over ``(num_blocks, block_size, n_kv, d)`` pools.  On the CPU rig (and
for moderate context lengths on TPU) XLA fuses the gather + matmul chain
well; the long-context TPU path would drop the same signature into a
Pallas kernel that walks the table block-by-block in VMEM (the
``ops/flash_attention.py`` machinery) — the call-site contract here is
written so that swap is local to this file.

Accumulators are float32 regardless of input dtype (bf16-safe softmax),
matching ``ops/attention.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = jnp.finfo(jnp.float32).min


def gather_kv(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize each sequence's paged KV as a padded dense view.

    pool: (num_blocks, block_size, n_kv, d) — the shared block pool.
    block_tables: (B, max_blocks) int32 — indices into the pool; entries
        past a sequence's allocation may be arbitrary valid indices
        (masking is by context length, not by table entry).

    Returns (B, max_blocks * block_size, n_kv, d).
    """
    n, bs, kv, d = pool.shape
    b, mb = block_tables.shape
    with jax.named_scope("paged_gather"):
        g = jnp.take(pool, block_tables.reshape(-1), axis=0)
        return g.reshape(b, mb * bs, kv, d)


def paged_attention_decode(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           ctx_lens: jax.Array, k_new: jax.Array,
                           v_new: jax.Array) -> jax.Array:
    """Single-token decode attention through a block table.

    q:       (B, H, D)        — query for the token being decoded.
    k_pool:  (N, bs, KV, D)   — shared key pool (this layer's view).
    v_pool:  (N, bs, KV, D)   — shared value pool.
    block_tables: (B, MAXB) int32.
    ctx_lens: (B,) int32      — tokens already IN the pool per sequence
                                (the new token is not in the pool yet).
    k_new, v_new: (B, KV, D)  — this token's key/value, attended in
                                explicitly: the pool is only read here,
                                and the runner's program writes the new
                                K/V into it after these reads.

    Returns (B, H, D) in q.dtype.
    """
    b, h, d = q.shape
    kvh = k_pool.shape[2]
    scale = 1.0 / math.sqrt(d)
    k_ctx = gather_kv(k_pool, block_tables)          # (B, T, KV, D)
    v_ctx = gather_kv(v_pool, block_tables)
    t = k_ctx.shape[1]
    with jax.named_scope("paged_attention"):
        if kvh != h:                                 # grouped-query heads
            rep = h // kvh
            k_ctx = jnp.repeat(k_ctx, rep, axis=2)
            v_ctx = jnp.repeat(v_ctx, rep, axis=2)
            k_new = jnp.repeat(k_new, rep, axis=1)
            v_new = jnp.repeat(v_new, rep, axis=1)
        logits = jnp.einsum("bhd,bkhd->bhk", q, k_ctx,
                            preferred_element_type=jnp.float32) * scale
        valid = jnp.arange(t)[None, :] < ctx_lens[:, None]      # (B, T)
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
        self_logit = jnp.einsum("bhd,bhd->bh", q, k_new,
                                preferred_element_type=jnp.float32) * scale
        logits = jnp.concatenate([logits, self_logit[..., None]], axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)                 # f32
        out = jnp.einsum("bhk,bkhd->bhd", probs[..., :-1],
                         v_ctx.astype(jnp.float32))
        out = out + probs[..., -1][..., None] * v_new.astype(jnp.float32)
        return out.astype(q.dtype)
