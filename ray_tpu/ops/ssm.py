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
