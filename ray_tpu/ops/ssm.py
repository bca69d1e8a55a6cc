"""State-space mixer pieces (Mamba-2's SSD): the causal conv, the chunked
scan for a whole prompt and the one-token recurrence for decode.

The recurrence, per head (P features, N state columns; heads share B and
C in groups)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t        S: (P, N)
    y_t = S_t C_t

``ssd_scan`` computes it for a sequence as Dao & Gu's chunked form: inside
a chunk a masked (Q, Q) matrix product, between chunks the carried state
(and between two calls, for a caller that hands the state of the one to the
next: ``state0``).  Lightning Attention's ``S_t = lambda S_{t-1} + k_t v_t^T,
o_t = S_t^T q_t`` is this recurrence with ``dt = 1``, ``A = log lambda``,
``B = k``, ``C = q``, ``x = v`` and a group a head
(``models/minicpm_sala.py``).
``ssm_step`` is the two lines above for one token.  Both are plain
``jax.numpy`` / ``lax`` in float32 (state and decay are float32 whatever
the activations are), and the matrix products ask for ``HIGHEST``: they
are a small part of a block next to its projections, and a state that
lives for a thousand tokens keeps what each step rounds away.

Padding.  A serving prompt is padded up to its bucket, and for a
recurrence the padding is poison: it would be folded into the state.
Both sequence functions take what freezes it: ``ssd_scan`` a ``dt`` that
the caller has set to 0 past the last real position (decay 1, input 0:
the state past it IS the state at it), ``causal_conv`` the ``last_pos``
whose window it returns.  ``D * x`` and the gate belong to the model.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


# --------------------------------------------------------------------- conv
def causal_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                last_pos: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Depthwise causal conv over (B, T, C): ``y_t = b + sum_k w[k] *
    x_{t-(K-1)+k}`` (``w[K-1]`` takes the current input), in float32;
    ``b`` None for a conv without a bias.

    Also returns the conv's *tail* at ``last_pos`` (traced scalar): the
    K-1 inputs ``x_{last_pos-K+2} .. x_{last_pos}`` as (B, K-1, C), zeros
    where the sequence had not begun, which is what :func:`conv_step`
    needs to go on from there; None without ``last_pos``."""
    k_w, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k_w - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = 0.0 if b is None else b.astype(jnp.float32)
    for k in range(k_w):
        y = y + w[k] * xp[:, k:k + t]
    if last_pos is None:
        return y, None
    return y, lax.dynamic_slice_in_dim(xp, last_pos + 1, k_w - 1, axis=1)


def conv_step(tail: jax.Array, x: jax.Array, w: jax.Array,
              b: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """One token through the conv: tail (R, K-1, C) float32, x (R, C) ->
    (y (R, C) float32, the tail one token on)."""
    window = jnp.concatenate([tail, x.astype(jnp.float32)[:, None]], axis=1)
    if b is None:
        return (w.astype(jnp.float32) * window).sum(1), window[:, 1:]
    y = b.astype(jnp.float32) + (w.astype(jnp.float32) * window).sum(1)
    return y, window[:, 1:]


# ------------------------------- the conv and its silu, one pass each way
# ``silu(causal_conv(x, w))`` for a mixer that trains (Gated DeltaNet's q |
# k | v).  As XLA runs the two lines it makes a float32 copy of the padded
# sequence and reads it four times at sublane offsets 0-3, writes the
# float32 product for the backward and sums the taps' gradient in a pass of
# its own: 47 ms a step at 2 x 8,192 x 8,192 where the bytes need 7 (ledger,
# PR 59).  On a TPU, with bf16 activations of whole 128-lane blocks and
# whole time blocks, two Pallas kernels do it instead, each under a name of
# its own (``causal_conv_fwd``, ``causal_conv_bwd``): bf16 in and out,
# float32 only in VMEM, ``CONV_STEP`` rows at a time so that a step's
# shifted copies stay in vector registers.  A position's K - 1 earlier
# inputs are a sublane roll of the rows with the 8 before them; across a
# time block's edge those 8 come from the tile before the block (a second
# BlockSpec on the same array), zeros at a sequence's start.  The backward
# is written out: it keeps ``x`` and ``w`` alone, makes the pre-activation
# again, walks the time blocks from the last to the first with the 8 rows
# of ``g = dy silu'(y)`` that follow a block in scratch (zeros past the
# end), and sums ``dw[k] = sum g_t x_{t-(K-1)+k}`` by sublane in a float32
# scratch across a channel block's batch rows and time blocks, written
# once.  Everything else takes the two lines, which are the definition.
CONV_ROWS = 1024        # positions a time block
CONV_LANES = 512        # channels a block, at most
CONV_STEP = 32          # rows of a block worked on at once
_TILE = 16              # rows of a bf16 tile: what is fetched before a block


def _last_rows(tile):
    """A (16, c) tile -> its last 8 rows, float32."""
    return tile.astype(jnp.float32)[_TILE - 8:]


def _conv_rows(taps, before, x):
    """x (R, c) float32, the 8 rows before it and the K taps (1, c) -> (x's
    copy each tap reads, ``x_{t-(K-1)+k}``: the last is x itself; their
    sum by the taps, in ``causal_conv``'s order)."""
    from jax.experimental.pallas import tpu as pltpu
    k_w = len(taps)
    rows = jnp.concatenate([before, x], axis=0)
    xs = [pltpu.roll(rows, k_w - 1 - k, 0)[8:] for k in range(k_w - 1)] + [x]
    y = taps[0] * xs[0]
    for tap, x_k in zip(taps[1:], xs[1:]):
        y = y + tap * x_k
    return xs, y


def _conv_fwd_kernel(x_ref, tile_ref, w_ref, y_ref, *, k_w, step):
    """x_ref, y_ref (bt, bc); tile_ref (16, bc) the rows before the block
    (the block's own first rows where there are none); w_ref (8, bc)
    float32, rows past K unused."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    taps = [w_ref[pl.ds(k, 1), :] for k in range(k_w)]
    before = jnp.where(pl.program_id(2) == 0, 0.0, _last_rows(tile_ref[...]))

    def rows(i, before):
        r = pl.multiple_of(i * step, step)
        x = x_ref[pl.ds(r, step), :].astype(f32)
        _, y = _conv_rows(taps, before, x)
        y_ref[pl.ds(r, step), :] = (y * jax.nn.sigmoid(y)).astype(y_ref.dtype)
        return x[step - 8:]

    lax.fori_loop(0, x_ref.shape[0] // step, rows, before)


def _conv_bwd_kernel(x_ref, tile_ref, dy_ref, w_ref, dx_ref, dw_ref,
                     after_ref, sums_ref, *, k_w, step):
    """Time blocks from the last to the first (program 2 counts from the
    end).  dx_ref (bt, bc); dw_ref (8, bc) float32, written at a channel
    block's last step; after_ref (8, bc) float32 the g of the rows that
    follow the block; sums_ref (8 K, bc) float32 the taps' sums by
    sublane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    b, t = pl.program_id(1), pl.program_id(2)
    last_b, last_t = pl.num_programs(1) - 1, pl.num_programs(2) - 1
    n = x_ref.shape[0] // step
    taps = [w_ref[pl.ds(k, 1), :] for k in range(k_w)]

    @pl.when((b == 0) & (t == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(t == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    def rows(r, before, after):
        """Rows r .. r + step with the 8 rows of x before them and the 8
        rows of g after them -> their first 8 rows of g."""
        x = x_ref[pl.ds(r, step), :].astype(f32)
        xs, y = _conv_rows(taps, before, x)
        sig = jax.nn.sigmoid(y)
        g = dy_ref[pl.ds(r, step), :].astype(f32) * (
            sig * (1.0 + y * (1.0 - sig)))
        for k, x_k in enumerate(xs):
            p = g * x_k
            sums_ref[8 * k:8 * k + 8] += sum(
                p[8 * j:8 * j + 8] for j in range(step // 8))
        # dx_t = sum_k w[k] g_{t+(K-1)-k}
        both = jnp.concatenate([g, after], axis=0)
        dx = taps[k_w - 1] * g
        for s in range(1, k_w):
            dx = dx + taps[k_w - 1 - s] * pltpu.roll(
                both, step + 8 - s, 0)[:step]
        dx_ref[pl.ds(r, step), :] = dx.astype(dx_ref.dtype)
        return g[:8]

    def from_the_end(j, after):
        r = pl.multiple_of((n - 1 - j) * step, step)
        return rows(r, _last_rows(x_ref[pl.ds(r - _TILE, _TILE), :]), after)

    after = lax.fori_loop(0, n - 1, from_the_end, after_ref[...])
    before = jnp.where(t == last_t, 0.0, _last_rows(tile_ref[...]))
    after_ref[...] = rows(0, before, after)

    @pl.when((b == last_b) & (t == last_t))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        for k in range(k_w):
            dw_ref[pl.ds(k, 1), :] = sums_ref[8 * k:8 * k + 8].sum(
                0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _conv_kernel(name, B, T, C, k_w, dtype, bt, bc, step, interpret):
    """``causal_conv_fwd`` (x, w -> y) or ``causal_conv_bwd`` (x, dy, w ->
    dx, dw) at one shape: grid (channel block, batch row, time block) of
    (bt, bc) blocks worked ``step`` rows at a time, w and dw (8, C)
    float32.  The ``pallas_call`` stands inside a jitted
    function of the kernel's name, so that the v5e's trace names it so and
    not ``tpu_custom_call.<n>`` (``ops/delta_rule._kernel``), the name
    ``kernels.custom_call_ms`` reads the flash kernels by."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    back = name == "causal_conv_bwd"
    nt = T // bt
    at = (lambda t: nt - 1 - t) if back else (lambda t: t)
    seq = jax.ShapeDtypeStruct((B, T, C), dtype)
    block = pl.BlockSpec((None, bt, bc), lambda c, b, t: (b, at(t), c))
    # the tile that ends where the block begins
    tile = pl.BlockSpec((None, _TILE, bc), lambda c, b, t: (
        b, jnp.maximum(at(t) * (bt // _TILE) - 1, 0), c))
    taps = (jax.ShapeDtypeStruct((8, C), jnp.float32),
            pl.BlockSpec((8, bc), lambda c, b, t: (0, c)))
    body = _conv_bwd_kernel if back else _conv_fwd_kernel

    def run(*args):
        return pl.pallas_call(
            functools.partial(body, k_w=k_w, step=step),
            grid=(C // bc, B, nt),
            in_specs=[block, tile] + [block] * back + [taps[1]],
            out_specs=[block, taps[1]] if back else block,
            out_shape=[seq, taps[0]] if back else seq,
            scratch_shapes=[pltpu.VMEM((8, bc), jnp.float32),
                            pltpu.VMEM((8 * k_w, bc), jnp.float32)] * back,
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                ("parallel", "arbitrary", "arbitrary") if back
                else ("parallel",) * 3)),
            interpret=interpret, name=name)(*args)
    run.__name__ = run.__qualname__ = name
    return jax.jit(run)


def _conv_call(name, x, w, interpret, *args):
    """Kernel ``name`` at the shape of x (B, T, C) and w (K, C), on
    ``args`` and the taps as the kernels read them."""
    bc = math.gcd(x.shape[2], CONV_LANES)    # lane blocks that divide C
    taps = jnp.pad(w.astype(jnp.float32), ((0, 8 - w.shape[0]), (0, 0)))
    return _conv_kernel(name, *x.shape, w.shape[0], jnp.dtype(x.dtype),
                        CONV_ROWS, bc, min(CONV_STEP, CONV_ROWS),
                        interpret)(*args, taps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_silu_kernels(x, w, interpret):
    """:func:`causal_conv_silu` in the kernels, whatever the backend."""
    return _conv_call("causal_conv_fwd", x, w, interpret, x, x)


def _conv_silu_fwd(x, w, interpret):
    return _conv_silu_kernels(x, w, interpret), (x, w)


def _conv_silu_bwd(interpret, res, dy):
    x, w = res
    dx, dw = _conv_call("causal_conv_bwd", x, w, interpret, x, x, dy)
    return dx, dw[:w.shape[0]].astype(w.dtype)


_conv_silu_kernels.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _conv_kernels_run(x, w) -> bool:
    """Whether a call runs in the kernels: on a TPU, bf16 activations,
    channels in whole 128-lane blocks, whole time blocks, at most 8 taps.
    Everything else (float32, the tests' narrow widths, a ragged T, the
    CPU) takes the XLA form."""
    return (jax.default_backend() == "tpu" and x.dtype == jnp.bfloat16
            and x.shape[2] % 128 == 0 and x.shape[1] % CONV_ROWS == 0
            and w.shape[0] <= 8)


def causal_conv_silu(x: jax.Array, w: jax.Array) -> jax.Array:
    """``silu(causal_conv(x, w))`` without a bias, in x's type: x (B, T,
    C), w (K, C).  float32 multiplies, adds and silu and one rounding on
    the way out, in both forms; which runs is read from the call
    (``_conv_kernels_run``)."""
    if _conv_kernels_run(x, w):
        return _conv_silu_kernels(x, w, False)
    return jax.nn.silu(causal_conv(x, w, None)[0]).astype(x.dtype)


# ------------------------- the gated output norm, one pass each way
# Gated DeltaNet's ``rms_norm(o over a head's lanes) * scale * silu(z)``
# (the norm gated AFTER: the published ``Qwen3NextRMSNormGated``).  As XLA
# runs the four lines of ``_gated_rms_norm_xla`` it passes over float32
# arrays of the sequence's size, forward, recomputation and backward: 32 ms
# a step at 2 x 8,192 x 32 heads of 128 where the bytes need 5 (ledger, PR
# 64).  On a TPU, with bf16 activations, heads of whole 128-lane tiles and
# whole time blocks, two Pallas kernels do it instead in the conv's manner
# (``gated_norm_fwd``, ``gated_norm_bwd``): bf16 in and out, float32 only in
# VMEM, ``NORM_STEP`` rows of one head at a time, a head's mean of squares
# a lane reduce.  The backward is written out: it keeps ``o``, ``z`` and
# ``scale`` alone, makes the head's ``rsqrt`` again, and sums ``dscale`` by
# sublane across a lane block's batch rows and time blocks in its float32
# result, which the caller folds over sublanes and heads.  Everything else
# takes the four lines, which are the definition.
NORM_ROWS = 1024        # positions a time block
NORM_LANES = 512        # lanes a block, at most: whole heads
NORM_STEP = 64          # rows of a head worked on at once


def _gated_rms_norm_xla(o, z, scale, eps):
    """o, z (..., H, dv), scale (dv,) -> o's type."""
    o32 = o.astype(jnp.float32)
    o32 = o32 * lax.rsqrt((o32 * o32).mean(-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)
    return (o32 * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def _norm_steps(ref, dv, step, rows, carry=None):
    """``rows(at, carry_h) -> carry_h`` over a (bt, bc) block ``step`` rows
    of one ``dv``-lane head at a time, ``at`` the index of those rows in the
    block's references; returns the heads' carries."""
    from jax.experimental import pallas as pl
    heads = range(ref.shape[1] // dv)

    def body(i, carry):
        r = pl.multiple_of(i * step, step)
        return [rows((pl.ds(r, step), slice(h * dv, (h + 1) * dv)), carry[h])
                for h in heads]

    return lax.fori_loop(0, ref.shape[0] // step, body,
                         [carry for _ in heads])


def _norm_fwd_kernel(o_ref, z_ref, s_ref, y_ref, *, dv, eps, step):
    """o_ref, z_ref, y_ref (bt, bc) of whole heads; s_ref (1, dv) float32."""
    f32 = jnp.float32
    s = s_ref[...]

    def rows(at, _):
        o, z = o_ref[at].astype(f32), z_ref[at].astype(f32)
        n = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * s
        y_ref[at] = (n * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)

    _norm_steps(o_ref, dv, step, rows)


def _norm_bwd_kernel(o_ref, z_ref, dy_ref, s_ref, do_ref, dz_ref, ds_ref,
                     *, dv, eps, step):
    """do_ref, dz_ref (bt, bc); ds_ref (8, bc) float32, the same block
    through a lane block's batch rows and time blocks: ``dscale``'s terms
    summed by sublane."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    s = s_ref[...]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def rows(at, sums):
        o, z = o_ref[at].astype(f32), z_ref[at].astype(f32)
        dy = dy_ref[at].astype(f32)
        r = lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        n = o * r
        sig = jax.nn.sigmoid(z)
        gate = z * sig
        dyn = dy * n
        dz_ref[at] = (dyn * s * (sig * (1.0 + z * (1.0 - sig)))
                      ).astype(dz_ref.dtype)
        # y = n s gate with n = o r: do = r (dn - n mean(dn n))
        dn = dy * s * gate
        do_ref[at] = (r * (dn - n * (dn * n).mean(-1, keepdims=True))
                      ).astype(do_ref.dtype)
        p = dyn * gate
        return sums + sum(p[8 * j:8 * j + 8] for j in range(step // 8))

    sums = _norm_steps(o_ref, dv, step, rows, jnp.zeros((8, dv), f32))
    ds_ref[...] += jnp.concatenate(sums, axis=1)


@functools.lru_cache(maxsize=None)
def _norm_kernel(name, B, T, C, dv, eps, dtype, bt, bc, step, interpret):
    """``gated_norm_fwd`` (o, z, scale -> y) or ``gated_norm_bwd`` (o, z,
    dy, scale -> do, dz, dscale's sums (8, C) float32) at one shape: grid
    (lane block, batch row, time block) of (bt, bc) blocks, scale (1, dv)
    float32.  Inside a jitted function of the kernel's name, as
    ``_conv_kernel``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    back = name == "gated_norm_bwd"
    seq = jax.ShapeDtypeStruct((B, T, C), dtype)
    block = pl.BlockSpec((None, bt, bc), lambda c, b, t: (b, t, c))
    scale = pl.BlockSpec((1, dv), lambda c, b, t: (0, 0))
    sums = (jax.ShapeDtypeStruct((8, C), jnp.float32),
            pl.BlockSpec((8, bc), lambda c, b, t: (0, c)))
    body = _norm_bwd_kernel if back else _norm_fwd_kernel

    def run(*args):
        return pl.pallas_call(
            functools.partial(body, dv=dv, eps=eps, step=step),
            grid=(C // bc, B, T // bt),
            in_specs=[block] * (3 if back else 2) + [scale],
            out_specs=[block, block, sums[1]] if back else block,
            out_shape=[seq, seq, sums[0]] if back else seq,
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                ("parallel", "arbitrary", "arbitrary") if back
                else ("parallel",) * 3)),
            interpret=interpret, name=name)(*args)
    run.__name__ = run.__qualname__ = name
    return jax.jit(run)


def _norm_call(name, o, scale, eps, interpret, *args):
    """Kernel ``name`` at the shape of o (B, T, C) and scale (dv,), on
    ``args`` and the scale as the kernels read it."""
    dv = scale.shape[0]
    bc = dv * math.gcd(o.shape[2] // dv, max(NORM_LANES // dv, 1))
    return _norm_kernel(name, *o.shape, dv, eps, jnp.dtype(o.dtype),
                        NORM_ROWS, bc, min(NORM_STEP, NORM_ROWS), interpret)(
        *args, scale.astype(jnp.float32)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gated_norm_kernels(o, z, scale, eps, interpret):
    """:func:`gated_rms_norm` on (B, T, C) in the kernels, whatever the
    backend."""
    return _norm_call("gated_norm_fwd", o, scale, eps, interpret, o, z)


def _gated_norm_fwd(o, z, scale, eps, interpret):
    return _gated_norm_kernels(o, z, scale, eps, interpret), (o, z, scale)


def _gated_norm_bwd(eps, interpret, res, dy):
    o, z, scale = res
    do, dz, sums = _norm_call("gated_norm_bwd", o, scale, eps, interpret,
                              o, z, dy)
    return do, dz, sums.reshape(-1, scale.shape[0]).sum(0).astype(scale.dtype)


_gated_norm_kernels.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def _norm_kernels_run(o, z, scale) -> bool:
    """Whether a call runs in the kernels: on a TPU, bf16 activations,
    heads of whole 128-lane tiles, whole time blocks.  Everything else
    (float32, the tests' narrow heads, a ragged T, the CPU) takes the XLA
    form."""
    return (jax.default_backend() == "tpu"
            and o.dtype == z.dtype == jnp.bfloat16
            and scale.shape[0] % 128 == 0 and o.shape[1] % NORM_ROWS == 0)


def gated_rms_norm(o: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float) -> jax.Array:
    """``rms_norm(o over each head's dv lanes, eps) * scale * silu(z)`` in
    o's type and shape: o, z (B, T, H dv) or (B, T, H, dv), scale (dv,).
    float32 arithmetic and one rounding on the way out, in both forms;
    which runs is read from the call (``_norm_kernels_run``)."""
    B, T, dv = *o.shape[:2], scale.shape[0]
    if _norm_kernels_run(o, z, scale):
        y = _gated_norm_kernels(o.reshape(B, T, -1), z.reshape(B, T, -1),
                                scale, eps, False)
    else:
        y = _gated_rms_norm_xla(o.reshape(B, T, -1, dv),
                                z.reshape(B, T, -1, dv), scale, eps)
    return y.reshape(o.shape)


# --------------------------------------------------------------------- scan
def _grouped(a: jax.Array, groups: int) -> jax.Array:
    """(..., H, *rest) -> (..., G, H/G, *rest) on the axis after batch and
    time: head h reads group h // (H/G)."""
    lead, h = a.shape[:2], a.shape[2]
    return a.reshape(*lead, groups, h // groups, *a.shape[3:])


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int, state0: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a sequence, chunked, from a zero state or from
    ``state0`` (B, H, P, N): the state a sequence's earlier positions left,
    for a caller that runs a long sequence as several calls.

    x (B, T, H, P); dt (B, T, H), after the softplus and 0 wherever the
    state must not move; a (H,) negative; b, c (B, T, G, N).  Any T: it
    is padded up to whole chunks with dt = 0.  Returns (y (B, T, H, P),
    the state after the last position (B, H, P, N)), float32."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                               (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (t + pad) // q
    dt = dt.astype(f32)
    # by chunk, heads by group: (B, c, Q, G, H/G, ...)
    xs = _grouped((x.astype(f32) * dt[..., None]), g).reshape(
        bsz, nc, q, g, h // g, p)
    la = _grouped(dt * a.astype(f32), g).reshape(bsz, nc, q, g, h // g)
    bc = b.astype(f32).reshape(bsz, nc, q, g, n)
    cc = c.astype(f32).reshape(bsz, nc, q, g, n)
    cum = jnp.cumsum(la, axis=2)            # log decay from the chunk's start
    # inside a chunk: y_l += sum_{s<=l} exp(cum_l - cum_s) (C_l . B_s) xs_s
    seg = cum[:, :, :, None] - cum[:, :, None, :]         # (B,c,Ql,Qs,G,Hg)
    keep = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])
    decay = jnp.exp(jnp.where(keep[None, None, :, :, None, None], seg,
                              -jnp.inf))
    scores = jnp.einsum("zclgn,zcsgn->zclsg", cc, bc, precision=_HI)
    y = jnp.einsum("zclsgh,zcsghp->zclghp", scores[..., None] * decay, xs,
                   precision=_HI)
    # what each chunk adds to the state at its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum)                # (B,c,Q,G,Hg)
    adds = jnp.einsum("zcsgn,zcsghp->zcghpn", bc, xs * to_end[..., None],
                      precision=_HI)
    whole = jnp.exp(cum[:, :, -1])                        # (B,c,G,Hg)

    def carry(state, chunk_in):
        add, dec = chunk_in
        return dec[..., None, None] * state + add, state

    state0 = jnp.zeros((bsz, g, h // g, p, n), f32) if state0 is None \
        else state0.astype(f32).reshape(bsz, g, h // g, p, n)
    state, entering = lax.scan(
        carry, state0, (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)               # (B,c,G,Hg,P,N)
    # the state a chunk entered with, read by each of its positions
    y = y + jnp.einsum("zclgn,zcghpn->zclghp", cc, entering,
                       precision=_HI) * jnp.exp(cum)[..., None]
    y = y.reshape(bsz, t + pad, h, p)[:, :t]
    return y, state.reshape(bsz, h, p, n)


def ssm_step(state: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The recurrence for one token of each row.

    state (R, H, P, N) float32; x (R, H, P); dt (R, H) after the
    softplus; a (H,); b, c (R, G, N).  Returns (y (R, H, P), the new
    state), float32.  Elementwise and a sum over N: the state is read
    once and written once, which is all the work there is."""
    f32 = jnp.float32
    r, h, p, n = state.shape
    g = b.shape[1]
    dt = dt.astype(f32)
    s = state.reshape(r, g, h // g, p, n)
    dec = jnp.exp(dt * a.astype(f32)).reshape(r, g, h // g, 1, 1)
    xs = (x.astype(f32) * dt[..., None]).reshape(r, g, h // g, p, 1)
    s = dec * s + xs * b.astype(f32)[:, :, None, None, :]
    y = (s * c.astype(f32)[:, :, None, None, :]).sum(-1)
    return y.reshape(r, h, p), s.reshape(r, h, p, n)


# ------------------------------------------ a decode step's tails, in place
# A served model keeps a conv's tail a sequence as a row of a store,
# ``(layers, R, (K - 1) C / lanes, lanes)`` float32: the K - 1 last inputs
# one behind another, laid ``lanes`` wide (whole (8, 128) tiles at C =
# 12,288; held as (K - 1, C) a row the TPU put the rows inside the tiles
# and as one (K - 1) C-wide line the kernel's (1, .) block needed a copy of
# the store that pads it to 8 sublanes, in every step: my chip runs, PR
# 59).  Gathered, stepped and scattered by XLA the 64 rows of a decode
# step are 64 dependent row copies each way (147 KB a row at C = 12,288:
# 0.9 ms a layer step where the bytes are 0.02: my chip run, PR 59), so on
# a TPU ``conv_step_rows`` steps them where they lie: a Pallas kernel a
# batch row, the row named by a prefetched scalar, the store aliased to
# the result.  Elsewhere it is the gather, :func:`conv_step` and the
# scatter.
def _conv_rows_kernel(rows_ref, layer_ref, x_ref, w_ref, t_ref, y_ref, t_out,
                      *, n_rows):
    """One batch row: x_ref (1, S, lanes) the new input (S lanes = C),
    w_ref (K, S, lanes) the taps, t_ref / t_out (1, 1, (K - 1) S, lanes)
    the row's tail, y_ref (1, S, lanes).  A batch row that names no row of
    the store reads its last row and writes it back as it is."""
    del layer_ref
    from jax.experimental import pallas as pl
    k_w, s = w_ref.shape[:2]
    live = rows_ref[pl.program_id(0)] < n_rows

    @pl.when(live)
    def _():
        window = [t_ref[0, 0, i * s:(i + 1) * s] for i in range(k_w - 1)]
        window.append(x_ref[0])
        y = w_ref[0] * window[0]
        for i in range(1, k_w):
            y = y + w_ref[i] * window[i]
        y_ref[0] = y
        for i in range(k_w - 1):
            t_out[0, 0, i * s:(i + 1) * s] = window[i + 1]

    @pl.when(jnp.logical_not(live))
    def _():
        t_out[...] = t_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _conv_rows_call(store, layer, rows, x, w, *, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_layer, n_rows, lines, lanes = store.shape
    (B, c), f32, k_w = x.shape, jnp.float32, w.shape[0]
    s = c // lanes

    def at_row(b, rows_ref, layer_ref):
        return (layer_ref[0], jnp.minimum(rows_ref[b], n_rows - 1), 0, 0)

    y, store = pl.pallas_call(
        functools.partial(_conv_rows_kernel, n_rows=n_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, s, lanes), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((k_w, s, lanes), lambda b, *_: (0, 0, 0)),
                pl.BlockSpec((1, 1, lines, lanes), at_row),
            ],
            out_specs=[
                pl.BlockSpec((1, s, lanes), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, lines, lanes), at_row),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, s, lanes), f32),
                   jax.ShapeDtypeStruct(store.shape, f32)],
        # operands: rows, layer, x, w, store -> the store is result 1
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="conv_step_rows",
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      x.astype(f32).reshape(B, s, lanes),
      w.astype(f32).reshape(k_w, s, lanes), store)
    return y.reshape(B, c), store


def conv_step_rows(store: jax.Array, layer, rows: jax.Array, x: jax.Array,
                   w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """:func:`conv_step` (no bias) on rows of a store, written back where
    they lie.

    store (layers, R, (K - 1) C / lanes, lanes) float32, a row the K - 1
    last inputs one behind another, ``lanes`` wide (``lanes`` divides C);
    ``layer`` which of its layers; ``rows`` (B,) int32 the
    row each batch row steps, DISTINCT, one ``>= R`` naming none (it reads
    the store's LAST row and writes it back as it is, so no batch row may
    name that one: the engine's staging row); x (B, C) the new inputs; w
    (K, C).  Returns (y (B, C) float32, the store with the named rows one
    token on)."""
    n_rows, lanes, c = store.shape[1], store.shape[3], x.shape[1]
    if jax.default_backend() == "tpu" and store.dtype == jnp.float32 \
            and lanes % 128 == 0 and (c // lanes) % 8 == 0:
        return _conv_rows_call(store, layer, rows, x, w)
    flat = store.reshape(-1, store.shape[2] * lanes)
    tail = flat[layer * n_rows + jnp.minimum(rows, n_rows - 1)]
    y, tail = conv_step(tail.reshape(-1, w.shape[0] - 1, c), x, w, None)
    at = jnp.where(rows < n_rows, layer * n_rows + rows, flat.shape[0])
    return y, flat.at[at].set(tail.reshape(tail.shape[0], -1),
                              mode="drop").reshape(store.shape)
