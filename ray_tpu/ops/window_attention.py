"""Causal attention of one chunk of a prompt over a staged K/V, under a
window or without one: the prefill of a model whose layers mix sliding-
window and full attention (``models/afmoe.py``).

A prompt runs in chunks of ``C`` positions.  A layer keeps the K/V of the
chunks before in a *staging* of whole chunk-sized segments, a position a
row of ``KV x D`` lanes, and says which chunk each segment holds
(``chunk_of``, negative: none).  Two stagings come of that, and one call
reads both:

* a full layer's holds every chunk of the prompt, segment ``s`` chunk
  ``s``;
* a window layer's is a ring of ``ceil(window / C) + 1`` segments
  (:func:`ring_segments`): chunk ``c`` lies in segment ``c mod n``, and the
  segment that chunk ``c`` overwrites held chunk ``c - n``, whose every
  position lies more than a window behind chunk ``c``'s first query
  (:func:`ring_chunks`).

A query at position ``t`` sees the keys ``t - window + 1 .. t`` (all of
``0 .. t`` without a window), its own among them: the chunk's own K/V is
staged before the call.

On a TPU, at a head size of whole lanes, a flash kernel over (KV head,
tile of queries, tile of keys) that builds its mask from the positions
(no mask is read) and runs only the tiles that the band of some query of
the tile crosses: a tile of keys wholly behind every window, past every
query or in an empty segment costs a grid step and no copy.  Elsewhere,
and the kernel's reference, the same mask over the whole staging in plain
``jax.numpy``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = jnp.finfo(jnp.float32).min
# queries and keys a grid step: 128 queries x 6 heads of a group are 768
# rows against 512 keys, 1.5 MiB of float32 scores in VMEM
_Q_TILE, _K_TILE = 128, 512
# the position of a tile of keys in a segment that holds no chunk: past
# every query
_FAR = 1 << 30


def ring_segments(window: int, chunk: int) -> int:
    """Segments of a window layer's ring: the chunks a query of the newest
    chunk can see into (``ceil(window / chunk)`` behind its own) and its
    own."""
    return -(-window // chunk) + 1


def ring_chunks(index, segments: int) -> jax.Array:
    """Which chunk each segment of a ring holds once chunk ``index`` is
    staged: segment ``s`` the newest chunk ``<= index`` that is ``s``
    modulo ``segments``; negative where the prompt has had none yet."""
    s = jnp.arange(segments, dtype=jnp.int32)
    return index - (index - s) % segments


def _plain(q, k_all, v_all, start, chunk_of, window):
    """:func:`chunk_attention` in plain ``jax.numpy``: the CPU's path and
    the kernel's reference."""
    t_q, kv, rep, d = q.shape
    s_len = k_all.shape[0]
    seg = s_len // chunk_of.shape[0]
    kpos = (chunk_of[:, None] * seg + jnp.arange(seg)).reshape(s_len)
    held = jnp.repeat(chunk_of >= 0, seg)
    qpos = start + jnp.arange(t_q)
    allowed = held[None, :] & (kpos[None, :] <= qpos[:, None])
    if window is not None:
        allowed &= kpos[None, :] > qpos[:, None] - window
    k = k_all[:, :kv * d].reshape(s_len, kv, d).astype(q.dtype)
    v = v_all[:, :kv * d].reshape(s_len, kv, d).astype(q.dtype)
    s = jnp.einsum("tgrd,sgd->gtrs", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    s = jnp.where(allowed[None, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("gtrs,sgd->tgrd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _band_kernel(pos_ref, live_ref, fetch_ref, start_ref, q_ref, k_ref, v_ref,
                 o_ref, m_ref, l_ref, acc_ref, *, scale, rep, window):
    """One (KV head, tile of queries, tile of keys): q_ref (1, 1, rep x bq,
    D), rows head-major (row r is query r % bq); k_ref / v_ref (bk, D)
    float32, the head's lanes of the staging's tile ``fetch_ref[i, j]``,
    whose first row holds position ``pos_ref[j]`` when the tile is live."""
    i, j = pl.program_id(1), pl.program_id(2)
    bk = k_ref.shape[0]
    bq = q_ref.shape[2] // rep

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[i, j] > 0)
    def _():
        q = q_ref[0, 0]                                     # (rep bq, D)
        k = k_ref[...].astype(q.dtype)
        v = v_ref[...].astype(q.dtype)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        qpos = start_ref[0] + i * bq \
            + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = pos_ref[j] + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        allowed = kpos <= qpos
        if window is not None:
            allowed &= kpos > qpos - window
        s = jnp.where(allowed[None], s.reshape(rep, bq, bk), NEG_INF
                      ).reshape(rep * bq, bk)
        m = m_ref[...]
        m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        # a row with nothing allowed so far carries exp(0) of masked
        # scores: the first allowed key's alpha = 0 wipes them, and every
        # query reads its own position at the latest
        p = jnp.exp(s - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(q.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def band_tiles(start, chunk_of, t_q: int, s_len: int,
               window: Optional[int], bq: int, bk: int):
    """What the kernel is told of a call's tiles: ``pos`` (key tiles,) the
    position of each tile's first row (``_FAR`` in a segment that holds
    nothing), ``live`` (query tiles, key tiles) whether the band of some
    query of the tile crosses it, ``fetch`` the tile to have in VMEM at a
    step: the step's own where it is live, else the last live one before
    it (no copy is made for a step that computes nothing)."""
    seg = s_len // chunk_of.shape[0]
    first = jnp.arange(s_len // bk, dtype=jnp.int32) * bk
    chunk = chunk_of[first // seg]
    pos = jnp.where(chunk >= 0, chunk * seg + first % seg, _FAR)
    q_first = start + jnp.arange(t_q // bq, dtype=jnp.int32) * bq
    live = pos[None, :] <= (q_first + bq - 1)[:, None]
    if window is not None:
        live &= pos[None, :] + bk - 1 > q_first[:, None] - window
    at = jnp.where(live, jnp.arange(s_len // bk, dtype=jnp.int32), -1)
    last = lax.cummax(at, axis=1)
    fetch = jnp.where(last >= 0, last, jnp.argmax(live, axis=1)[:, None])
    return pos, live.astype(jnp.int32), fetch.astype(jnp.int32)


def _band_flash(q, k_all, v_all, start, chunk_of, window, *, interpret=False):
    """:func:`chunk_attention` as one Pallas call."""
    from jax.experimental.pallas import tpu as pltpu

    t_q, kv, rep, d = q.shape
    s_len = k_all.shape[0]
    seg = s_len // chunk_of.shape[0]
    bq, bk = min(_Q_TILE, t_q), min(_K_TILE, seg)
    n_qt, n_kt = t_q // bq, s_len // bk
    start = jnp.asarray(start, jnp.int32)
    pos, live, fetch = band_tiles(start, chunk_of, t_q, s_len, window, bq, bk)
    # rows of a tile head-major: (KV, q tiles, rep x bq, D)
    rows = q.reshape(n_qt, bq, kv, rep, d).transpose(2, 0, 3, 1, 4) \
        .reshape(kv, n_qt, rep * bq, d)

    def keys(g, i, j, pos_ref, live_ref, fetch_ref, start_ref):
        return (fetch_ref[i, j], g)

    tile = lambda g, i, j, *prefetched: (g, i, 0, 0)           # noqa: E731
    out = pl.pallas_call(
        functools.partial(_band_kernel, scale=1.0 / math.sqrt(d), rep=rep,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(kv, n_qt, n_kt),
            in_specs=[
                pl.BlockSpec((1, 1, rep * bq, d), tile),
                pl.BlockSpec((bk, d), keys),
                pl.BlockSpec((bk, d), keys),
            ],
            out_specs=pl.BlockSpec((1, 1, rep * bq, d), tile),
            scratch_shapes=[
                pltpu.VMEM((rep * bq, 1), jnp.float32),
                pltpu.VMEM((rep * bq, 1), jnp.float32),
                pltpu.VMEM((rep * bq, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((kv, n_qt, rep * bq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="band_prefill" if window is not None else "causal_prefill",
    )(pos, live, fetch, start.reshape(1), rows, k_all, v_all)
    return out.reshape(kv, n_qt, rep, bq, d).transpose(1, 3, 0, 2, 4) \
        .reshape(t_q, kv, rep, d)


def chunk_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array, start,
                    chunk_of: jax.Array,
                    window: Optional[int] = None) -> jax.Array:
    """Causal softmax attention of a chunk's queries over a staged K/V.

    q (T, KV, R, D), the queries of positions ``start .. start + T - 1``;
    k_all, v_all (S, F) float32, lane-flat (``F >= KV x D``), ``S`` whole
    segments of ``S / len(chunk_of)`` rows; ``chunk_of`` (segments,) int32:
    the chunk a segment holds (its rows the positions ``chunk x segment
    ..``), negative for none; ``window``: a query sees that many positions,
    its own the last (None: every position up to its own).  Returns (T,
    KV, R, D) in ``q.dtype``."""
    t_q, kv, _, d = q.shape
    seg = k_all.shape[0] // chunk_of.shape[0]
    if jax.default_backend() == "tpu" and d % 128 == 0 \
            and k_all.shape[1] == kv * d and t_q % min(_Q_TILE, t_q) == 0 \
            and t_q % 8 == 0 and seg % min(_K_TILE, seg) == 0 and seg % 8 == 0:
        return _band_flash(q, k_all, v_all, start, chunk_of, window)
    return _plain(q, k_all, v_all, start, chunk_of, window)
