"""Pipeline parallelism: single-program microbatch pipelining over the
``pipeline`` mesh axis.

Reference contrast (SURVEY.md §2.4): Ray core has no pipeline parallelism —
its ecosystem reaches PP by placement-grouping actors around DeepSpeed/Alpa,
shipping activations through the object store between stage processes.  The
TPU-native inversion: all stages live in ONE compiled SPMD program; stage
s→s+1 activation transfer is a ``ppermute`` over the ``pipeline`` mesh axis
(ICI neighbor hop), and the fill/drain schedule is a ``lax.scan`` — XLA
overlaps the permute with the next microbatch's compute.

Schedule: GPipe-style fill/drain over ``num_microbatches`` microbatches and
S stages: tick t runs microbatch ``t - s`` on stage ``s``; bubble fraction is
``(S-1)/(num_microbatches + S - 1)``, so pick num_microbatches >= 4*S.
Gradients flow through the schedule automatically — ``ppermute`` and
``lax.scan`` are differentiable, so the same program serves fwd+bwd (the
backward pass is the reversed pipeline XLA derives).

Layout contract: stage parameters are pytrees whose leaves carry a leading
``num_stages`` axis sharded ``P("pipeline", ...)`` (the stacked-layer layout
``models/gpt2.py`` already uses for ``lax.scan`` over blocks — reshaped from
(L, ...) to (S, L/S, ...) by ``stack_stages``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stages(params: Any, num_stages: int) -> Any:
    """(L, ...) stacked-layer params → (S, L/S, ...) stage-major layout."""
    def leaf(p):
        L = p.shape[0]
        if L % num_stages:
            raise ValueError(
                f"{L} layers not divisible by {num_stages} pipeline stages")
        return p.reshape(num_stages, L // num_stages, *p.shape[1:])
    return jax.tree_util.tree_map(leaf, params)


def unstack_stages(params: Any) -> Any:
    """Inverse of :func:`stack_stages`."""
    return jax.tree_util.tree_map(
        lambda p: p.reshape(p.shape[0] * p.shape[1], *p.shape[2:]), params)


def split_microbatches(batch: Any, num_microbatches: int) -> Any:
    """(B, ...) → (num_microbatches, B/num_microbatches, ...)."""
    def leaf(x):
        B = x.shape[0]
        if B % num_microbatches:
            raise ValueError(f"batch {B} not divisible by "
                             f"{num_microbatches} microbatches")
        return x.reshape(num_microbatches, B // num_microbatches, *x.shape[1:])
    return jax.tree_util.tree_map(leaf, batch)


def merge_microbatches(y: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]), y)


def pipeline_apply(
        stage_fn: Callable[[Any, jax.Array], jax.Array],
        stage_params: Any,
        x_micro: jax.Array,
        *,
        mesh: Mesh,
        axis: str = "pipeline",
        remat: bool = True) -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline over microbatched input.

    ``stage_fn(params_for_one_stage, x) -> y`` must preserve the activation
    shape (the transformer-block contract).  ``stage_params`` leaves have
    leading dim S (see :func:`stack_stages`); ``x_micro`` is
    ``(num_microbatches, mb, ...)``.  Returns ``(num_microbatches, mb, ...)``
    outputs (the last stage's results, replicated over the pipeline axis).

    Everything except the ``pipeline`` axis stays in GSPMD-automatic mode, so
    data/tensor/context sharding of the microbatch dims composes with this.
    """
    S = mesh.shape[axis]
    num_micro = x_micro.shape[0]
    if S == 1:
        f = jax.checkpoint(stage_fn) if remat else stage_fn
        squeezed = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        return jax.vmap(lambda xb: f(squeezed, xb))(x_micro)
    if num_micro < S:
        raise ValueError(f"need >= {S} microbatches for {S} stages")

    fwd = [(i, (i + 1) % S) for i in range(S)]
    f = jax.checkpoint(stage_fn) if remat else stage_fn

    # XLA-CPU workaround: the backward pass psums the replicated input's
    # cotangent over the pipeline axis, and bf16 all-reduces crash the CPU
    # backend's ChangeOpDataType pass.  Cast at the boundary on CPU only;
    # TPU keeps bf16 end to end.
    io_dtype = x_micro.dtype
    cast_io = (jax.default_backend() == "cpu" and io_dtype == jnp.bfloat16)
    if cast_io:
        x_micro = x_micro.astype(jnp.float32)

    def per_shard(local_params, x_mb):
        if cast_io:
            x_mb = x_mb.astype(io_dtype)
        # local_params leaves: (1, L/S, ...) — this stage's slice
        p = jax.tree_util.tree_map(lambda q: q[0], local_params)
        stage = jax.lax.axis_index(axis)
        T = num_micro + S - 1
        # emit buffer in f32 under the CPU workaround: all_gather's
        # *transpose* is a reduce-scatter, which must not be bf16 either
        ys0 = jnp.zeros(x_mb.shape,
                        jnp.float32 if cast_io else x_mb.dtype)
        state0 = jnp.zeros_like(x_mb[0])

        def tick(carry, t):
            state, ys = carry
            # stage 0 ingests microbatch t (clamped during drain); others
            # consume the activation ppermute'd from stage s-1 last tick
            inp = jnp.where(stage == 0,
                            x_mb[jnp.minimum(t, num_micro - 1)], state)
            out = f(p, inp)
            nxt = jax.lax.ppermute(out, axis, fwd)
            # last stage emits microbatch t-(S-1) once the pipe is full
            idx = jnp.clip(t - (S - 1), 0, num_micro - 1)
            emit = jnp.logical_and(stage == S - 1, t >= S - 1)
            ys = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(
                    ys, out.astype(ys.dtype), idx, 0), ys)
            return (nxt, ys), None

        (_, ys), _ = jax.lax.scan(tick, (state0, ys0), jnp.arange(T))
        # replicate the last stage's buffer to every pipeline rank
        # (all_gather + index, not a masked psum: reductions over bf16 hit
        # an XLA-CPU ChangeOpDataType crash when cloning the all-reduce)
        return jax.lax.all_gather(ys, axis)[S - 1]

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    from jax import shard_map
    out = shard_map(
        per_shard, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        axis_names=frozenset({axis}), check_vma=False,
    )(stage_params, x_micro)
    return out.astype(io_dtype) if cast_io else out


def pick_num_microbatches(batch_size: int, num_stages: int,
                          target_multiple: int = 4) -> int:
    """Largest divisor of batch_size that is <= target_multiple * stages
    (enough microbatches to amortize the fill/drain bubble)."""
    want = max(num_stages, min(batch_size, target_multiple * num_stages))
    for m in range(want, 0, -1):
        if batch_size % m == 0 and m >= num_stages:
            return m
    return 1
