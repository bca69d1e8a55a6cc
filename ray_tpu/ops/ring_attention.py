"""Ring attention: context-parallel attention over an ICI ring.

Greenfield TPU component (SURVEY.md §5.7 — the reference has no sequence
parallelism).  The sequence axis is sharded over the ``context`` mesh axis;
each device holds a contiguous chunk of Q/K/V.  K/V blocks rotate around
the ring via ``lax.ppermute`` (XLA lowers this to ICI collective-permute,
overlapping the transfer of step s+1's block with step s's compute), while
each device accumulates its queries' attention with the online-softmax
update from ``ray_tpu.ops.attention``.

Activation memory per device is O(T_local·D); the full T×T score matrix is
never materialized anywhere.  Differentiable end-to-end: ``lax.scan`` +
``ppermute`` both have transpose rules, so reverse-mode runs the ring
backwards automatically.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import (causal_mask, dense_attention,
                                   flash_finalize, flash_update)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str, axis_size: int,
                   causal: bool = True) -> jax.Array:
    """Per-shard ring attention; call inside shard_map.

    q/k/v: (B, T_local, H, D) — this device's contiguous sequence chunk;
    chunk index = ``lax.axis_index(axis_name)``.  Returns (B, T_local, H, D).
    """
    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    me = lax.axis_index(axis_name)
    q_pos = me * T + jnp.arange(T)

    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    m0 = jnp.full((B, H, T), jnp.finfo(jnp.float32).min)
    l0 = jnp.zeros((B, H, T), jnp.float32)

    def body(step, carry, kv):
        o, m, l = carry
        kc, vc = kv
        # Step s processes chunk (me - s) mod n: step 0 is the diagonal
        # block, which always has a valid key for every row (causal q>=k
        # includes self) — the flash_update masking contract.
        src = (me - step) % axis_size
        if causal:
            k_pos = src * T + jnp.arange(T)
            mask = causal_mask(q_pos, k_pos)[None, None]
        else:
            mask = None
        return flash_update(o, m, l, q, kc, vc, mask, scale)

    # ring_scan issues each rotation BEFORE the update consuming the
    # resident chunk, so XLA pipelines transfer s+1 under compute s (the
    # same double-buffer schedule ops/collective_matmul.py rides).
    from ray_tpu.ops.collective_matmul import ring_scan
    o, _, l = ring_scan(body, (o0, m0, l0), (k, v),
                        axis_name=axis_name, axis_size=axis_size)
    return flash_finalize(o, l, q.dtype)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           mesh, axis_name: str = "context",
                           batch_axes=("data", "fsdp"),
                           head_axis: Optional[str] = "tensor",
                           causal: bool = True) -> jax.Array:
    """GSPMD-land wrapper: global (B,T,H,D) arrays → shard_map ring.

    Inputs are (re)sharded to [batch_axes, context, head_axis, None]; the
    ring runs over ICI neighbors of the ``context`` axis.
    """
    axis_size = mesh.shape[axis_name]
    if axis_size == 1:
        return dense_attention(q, k, v, causal=causal)
    spec = P(tuple(a for a in batch_axes if a in mesh.shape), axis_name,
             head_axis if head_axis in mesh.shape else None, None)
    inner = partial(ring_attention, axis_name=axis_name,
                    axis_size=axis_size, causal=causal)
    from jax import shard_map
    return shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)

