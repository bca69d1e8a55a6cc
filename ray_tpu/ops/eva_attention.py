"""EVA's fold (EvaByte's chunked linearised attention; Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023, in the form the EvaByte release
gives it): a window of ``window`` positions, once it has closed, is kept as
ONE key and ONE value for every ``chunk`` positions.

For chunk ``j`` of a head, with the head's two learned vectors ``phi`` and
``mu`` (``head_dim`` wide)::

    a_s  = softmax over the chunk's positions s of (k_s . phi) / sqrt(head_dim)
    kf_j = sum_s a_s k_s + mu            vf_j = sum_s a_s v_s

A query then sees its own window exactly and every closed window's folded
pairs as so many more keys and values, through one softmax at one scale.  So
nothing here is an attention kernel: a folded row lies where an exact one
would (``serve/llm/kv_cache.py``, the table that shrinks), a prompt's chunk
is causal attention in the coordinates of the rows a sequence HOLDS
(``ops/window_attention.chunk_attention``), and a decode step is the paged
walk over the shrunk table (``ops/paged_attention.paged_attention_decode``).

The fold reads a window's K and V once and writes a sixteenth of it: bound by
bytes, elementwise products and sums in float32, left to XLA.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def fold_rows(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
              chunk: int):
    """k, v (T, H, D), ``T`` whole chunks; phi, mu (H, D) -> (kf, vf) (T /
    chunk, H, D) float32.  Sums and not matmuls: every product is a float32
    one on any backend."""
    f32 = jnp.float32
    T, H, D = k.shape
    with jax.named_scope("eva_fold"):
        kc = k.astype(f32).reshape(T // chunk, chunk, H, D)
        vc = v.astype(f32).reshape(T // chunk, chunk, H, D)
        scores = (kc * phi.astype(f32)).sum(-1) / math.sqrt(D)  # (J, c, H)
        a = jax.nn.softmax(scores, axis=1)[..., None]
        return (a * kc).sum(1) + mu.astype(f32), (a * vc).sum(1)
