"""Ulysses-style sequence parallelism: all-to-all over heads.

Greenfield TPU component (SURVEY.md §5.7).  Alternative to ring attention
when ``n_heads >= context_parallel_size``: instead of rotating KV blocks,
one all-to-all re-shards (B, T/n, H, D) → (B, T, H/n, D) so every device
holds FULL sequences for a subset of heads, runs plain (fused) attention
locally, and a second all-to-all restores sequence sharding.

Cost: 2 all-to-alls of the activations vs ring's (n-1) KV rotations —
cheaper on ICI for moderate sequence lengths; ring wins when T is huge
(all-to-all volume scales with T) or when H < ring size.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import dense_attention


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str, causal: bool = True) -> jax.Array:
    """Per-shard Ulysses attention; call inside shard_map.

    q/k/v: (B, T_local, H, D) sequence-sharded; H must be divisible by the
    axis size.  Returns (B, T_local, H, D).
    """
    # (B, T/n, H, D) -> (B, T, H/n, D): split heads across the axis, gather
    # the sequence.  tiled=True concatenates rather than stacking.
    a2a = partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    qg = a2a(q, split_axis=2, concat_axis=1)
    kg = a2a(k, split_axis=2, concat_axis=1)
    vg = a2a(v, split_axis=2, concat_axis=1)
    # Full sequence present locally: positions are global, plain causal mask.
    out = dense_attention(qg, kg, vg, causal=causal)
    return a2a(out, split_axis=1, concat_axis=2)


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                              mesh, axis_name: str = "context",
                              batch_axes=("data", "fsdp"),
                              causal: bool = True) -> jax.Array:
    """GSPMD-land wrapper: global (B,T,H,D) → shard_map Ulysses.

    Heads stay UNSHARDED over ``tensor`` here: Ulysses consumes the head
    dimension for sequence parallelism (head_parallel = context axis).
    """
    n = mesh.shape[axis_name]
    if n == 1:
        return dense_attention(q, k, v, causal=causal)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs n_heads ({q.shape[2]}) divisible by "
            f"{axis_name} axis size ({n})")
    spec = P(tuple(a for a in batch_axes if a in mesh.shape), axis_name,
             None, None)
    inner = partial(ulysses_attention, axis_name=axis_name, causal=causal)
    from jax import shard_map
    return shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)

