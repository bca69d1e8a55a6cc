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
