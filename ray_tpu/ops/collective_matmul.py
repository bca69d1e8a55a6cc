"""Decomposed sharded matmuls: collective legs hidden behind compute.

The MFU plateau (driver runs r04→r05: 0.505→0.508 with ``mfu_vs_delivered``
0.64) is unoverlapped collectives: GSPMD materializes a model-parallel
matmul as ``all-gather → one big matmul`` or ``one big matmul → psum /
reduce-scatter``, and the collective leg serializes against the compute
it feeds.  The fix (Wang et al. 2023, "Overlap Communication with
Dependent Computation via Decomposition") is to decompose both shapes
into chunked ``lax.ppermute`` rings — the machinery already proven by
``ops/ring_attention.py`` — so chunk s+1's transfer rides ICI while
chunk s's partial product is on the MXU:

- :func:`all_gather_matmul` — ``Y = allgather(X) @ W`` without ever
  materializing ``allgather(X)``: each ring step matmuls the resident
  X chunk against the local W shard while the next chunk is in flight.
- :func:`matmul_reduce_scatter` — ``Y = reducescatter(X @ W)`` without
  ever materializing the full partial product: the accumulator rotates
  around the ring and each device adds its partial for the chunk
  currently passing through, computed while the accumulator was in
  flight.

Both carry custom VJPs so reverse-mode overlaps the same way: the two
primitives are each other's transpose (d/dX of all-gather-matmul IS a
matmul-reduce-scatter, and vice versa), and the dW reductions run as
one more ring.  Everything is ``lax.scan`` + ``ppermute``, so the pair
nests inside ``shard_map`` / ``jax.checkpoint`` / ``lax.scan`` layers
exactly like ring attention does.

These are PER-SHARD primitives: call inside ``shard_map`` with
``axis_name`` bound.  ``ray_tpu/models/gpt2.py`` routes the qkv /
attn-out / MLP projections through them when the ambient mesh has a
model axis (``seq`` or ``tensor`` > 1).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def ring_scan(body: Callable[[Any, Any, Any], Any], carry: Any,
              rotating: Any, *, axis_name: str, axis_size: int) -> Any:
    """Run ``axis_size`` steps of a ppermute ring over ``rotating``.

    ``body(step, carry, rotating) -> carry`` consumes the rotating block
    resident at this step; after step ``s`` the device holds the block
    that started ``s`` hops upstream (source index ``(me - s) % n`` for
    the canonical ``d → d+1`` ring).  The rotation for step s+1 is
    issued BEFORE body runs, so it carries no data dependence on body's
    compute and XLA's latency-hiding scheduler overlaps the transfer
    with the matmul/attention work (double buffering).  The final
    rotation is redundant in exact arithmetic but kept so every step is
    the same program — the shape XLA software-pipelines.
    """
    perm = [(d, (d + 1) % axis_size) for d in range(axis_size)]

    def scan_body(c, step):
        inner, rot = c
        rot_next = jax.tree_util.tree_map(
            lambda a: lax.ppermute(a, axis_name, perm), rot)
        inner = body(step, inner, rot)
        return (inner, rot_next), None

    (carry, _), _ = lax.scan(scan_body, (carry, rotating),
                             jnp.arange(axis_size))
    return carry


def _chunk(x: jax.Array, i, t: int) -> jax.Array:
    """Rows ``[i*t, (i+1)*t)`` of x's second-to-last dim (traced i ok)."""
    return lax.dynamic_slice_in_dim(x, i * t, t, axis=-2)


def _put_chunk(out: jax.Array, y: jax.Array, i, t: int) -> jax.Array:
    return lax.dynamic_update_slice_in_dim(out, y, i * t, axis=-2)


def _xt_dot(x: jax.Array, g: jax.Array) -> jax.Array:
    """dW partial: contract x (..., t, k) with g (..., t, n) over every
    dim but the last → (k, n) f32."""
    kdim, ndim = x.shape[-1], g.shape[-1]
    xf = x.reshape(-1, kdim)
    gf = g.reshape(-1, ndim)
    return jax.lax.dot_general(xf, gf, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# all-gather-matmul:  Y = allgather_rows(X) @ W
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def all_gather_matmul(x: jax.Array, w: jax.Array, axis_name: str,
                      axis_size: int) -> jax.Array:
    """x (..., t, k) local rows; w (k, n) local columns →
    (..., t*axis_size, n): the full gathered row space times this
    device's W shard, gather hidden behind the chunk matmuls."""
    return _ag_matmul_fwd_impl(x, w, axis_name, axis_size)


def _ag_matmul_fwd_impl(x, w, axis_name, axis_size):
    if axis_size == 1:
        return x @ w
    t = x.shape[-2]
    me = lax.axis_index(axis_name)
    out = jnp.zeros(x.shape[:-2] + (t * axis_size, w.shape[-1]),
                    jnp.result_type(x.dtype, w.dtype))

    def body(step, out, xc):
        src = (me - step) % axis_size
        return _put_chunk(out, xc @ w, src, t)

    return ring_scan(body, out, x, axis_name=axis_name,
                     axis_size=axis_size)


def _ag_matmul_fwd(x, w, axis_name, axis_size):
    return _ag_matmul_fwd_impl(x, w, axis_name, axis_size), (x, w)


def _ag_matmul_bwd(axis_name, axis_size, res, g):
    x, w = res
    # dX: every device's W shard saw every X chunk, so chunk j's grad is
    # Σ over devices of g[chunk j] @ Wᵀ — exactly a matmul-reduce-scatter
    # (the transpose ring overlaps the same way the forward did).
    dx = _mm_rs_fwd_impl(g, w.T, axis_name, axis_size,
                         acc_dtype=jnp.float32).astype(x.dtype)
    if axis_size == 1:
        dw = _xt_dot(x, g).astype(w.dtype)
        return dx, dw
    # dW = gathered(X)ᵀ @ g: one more ring over the X chunks, each step
    # contracting the resident chunk with its rows of g while the next
    # chunk is in flight.
    t = x.shape[-2]
    me = lax.axis_index(axis_name)
    dw0 = jnp.zeros(w.shape, jnp.float32)

    def body(step, dw, xc):
        src = (me - step) % axis_size
        return dw + _xt_dot(xc, _chunk(g, src, t))

    dw = ring_scan(body, dw0, x, axis_name=axis_name, axis_size=axis_size)
    return dx, dw.astype(w.dtype)


all_gather_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


# ---------------------------------------------------------------------------
# matmul-reduce-scatter:  Y = reducescatter_rows(X @ W)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis_name: str,
                          axis_size: int) -> jax.Array:
    """x (..., t*axis_size, k) full rows of this device's partial
    operand; w (k, n) → (..., t, n): rows chunk-summed across the ring,
    this device keeping chunk ``axis_index``.  The psum/reduce-scatter
    leg never exists as one collective: partial chunks are computed
    while the accumulator is in flight."""
    return _mm_rs_fwd_impl(x, w, axis_name, axis_size)


def _mm_rs_fwd_impl(x, w, axis_name, axis_size, acc_dtype=jnp.float32):
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if axis_size == 1:
        return (x @ w).astype(out_dtype)
    n = axis_size
    t = x.shape[-2] // n
    me = lax.axis_index(axis_name)
    perm = [(d, (d + 1) % n) for d in range(n)]

    # Chunk c is born at device c+1 (its partial, no add), rides the ring
    # through c+2 … and ends at device c having accumulated every
    # device's partial: at step s, device d adds its partial for chunk
    # (d - 1 - s) % n.  The ppermute for step s is issued before step
    # s's partial matmul, so transfer and compute overlap.
    acc = (_chunk(x, (me - 1) % n, t) @ w).astype(acc_dtype)

    def body(carry, step):
        acc = carry
        acc_in = lax.ppermute(acc, axis_name, perm)
        part = _chunk(x, (me - 1 - step) % n, t) @ w
        return acc_in + part.astype(acc_dtype), None

    acc, _ = lax.scan(body, acc, jnp.arange(1, n))
    return acc.astype(out_dtype)


def _mm_rs_fwd(x, w, axis_name, axis_size):
    return _mm_rs_fwd_impl(x, w, axis_name, axis_size), (x, w)


def _mm_rs_bwd(axis_name, axis_size, res, g):
    x, w = res
    # dX: the full row space re-materializes from the per-device chunk
    # grads times Wᵀ — exactly an all-gather-matmul.
    dx = _ag_matmul_fwd_impl(g, w.T, axis_name, axis_size).astype(x.dtype)
    if axis_size == 1:
        return dx, _xt_dot(x, g).astype(w.dtype)
    # dW = Xᵀ @ gathered(g): rotate the local chunk grad around the ring,
    # each step contracting it with the matching rows of X.
    t = g.shape[-2]
    me = lax.axis_index(axis_name)
    dw0 = jnp.zeros(w.shape, jnp.float32)

    def body(step, dw, gc):
        src = (me - step) % axis_size
        return dw + _xt_dot(_chunk(x, src, t), gc)

    dw = ring_scan(body, dw0, g, axis_name=axis_name, axis_size=axis_size)
    return dx, dw.astype(w.dtype)


matmul_reduce_scatter.defvjp(_mm_rs_fwd, _mm_rs_bwd)


# ---------------------------------------------------------------------------
# Reference (un-decomposed) implementations: the numerics oracle for the
# tests and the A/B baseline for bench overlap accounting.
# ---------------------------------------------------------------------------

def all_gather_matmul_reference(x: jax.Array, w: jax.Array,
                                axis_name: str,
                                axis_size: int) -> jax.Array:
    """The GSPMD shape being decomposed: one all-gather, one matmul."""
    if axis_size == 1:
        return x @ w
    xg = lax.all_gather(x, axis_name, axis=-2, tiled=True)
    return xg @ w


def matmul_reduce_scatter_reference(x: jax.Array, w: jax.Array,
                                    axis_name: str,
                                    axis_size: int) -> jax.Array:
    """One matmul, one psum_scatter — the serialized collective leg."""
    y = x @ w
    if axis_size == 1:
        return y
    return lax.psum_scatter(y, axis_name, scatter_dimension=y.ndim - 2,
                            tiled=True)


def model_parallel_sizes(mesh) -> Tuple[int, int]:
    """(seq, tensor) axis sizes of a mesh (1 when absent) — the gate the
    model layer uses to decide whether the decomposed path is live."""
    shape = dict(getattr(mesh, "shape", {}) or {})
    return int(shape.get("seq", 1)), int(shape.get("tensor", 1))
