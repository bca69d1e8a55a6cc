"""The gated delta rule (Gated DeltaNet): a recurrent layer whose state
forgets by a gate and overwrites by a key, for a whole sequence in chunked
form with a backward, and the one-token recurrence it equals.

The recurrence, per value head (state ``S``: dk x dv, float32, zero at the
start; ``g_t <= 0`` the log of the decay, ``beta_t`` in (0, 1))::

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)         what the state has wrong about k_t
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

The transition ``exp(g)(I - beta k k^T)`` is no diagonal, so
``ops/ssm.ssd_scan``'s chunked form does not compute it.  The chunked form
here (Yang et al., "Gated Delta Networks"; transformers'
``torch_chunk_gated_delta_rule``): in a chunk of C positions with ``c_i =
sum_{j<=i} g_j``, the corrections ``d`` of the chunk solve a unit lower
triangular system, because each reads the state that the earlier ones of
the chunk wrote::

    A_ij = beta_i (k_i . k_j) exp(c_i - c_j)   (j < i)    T = (I + A)^-1
    U = T (beta V)        W = T (beta exp(c) K)
    V' = U - W S0                                    the chunk's d, (C, dv)
    o_i = exp(c_i) q_i^T S0 + sum_{j<=i} exp(c_i - c_j) (q_i . k_j) v'_j
    S_end = exp(c_C) S0 + sum_j exp(c_C - c_j) k_j v'_j^T

Two forms of that one algorithm, at the same precisions; which runs is
read from the call (``_kernels_run``), never set, whichever entry takes
it:

*Pallas kernels* (PR 58), on a TPU with bf16 activations, chunks of 64
and heads of whole 128-lane blocks (the Qwen3-Next cell).  Four, each
under a name of its own, q, k, v and o read and written as the
projections leave them, (B, T, heads x 128) by column blocks, the R value
heads of a key head in one grid step (k . k and q . k made once for
both), ``STEP_CHUNKS`` chunks a step.  Two entries hand them their
operands.  ``gated_delta_rule`` takes q, k and v as three arrays, q and k
normed by the caller.  ``gated_delta_rule_qkv`` (PR 70, the mixer's) takes
a DeltaNet mixer's conv output whole and unnormed, (B, T, q | k | v): the
same kernels under a static switch read the three as column blocks of
the ONE array (k's behind the G blocks of q, v's behind both: no slice
of it is made) and l2-norm the (64, 128) block of q and of k they have
loaded, where a head's norm is a reduce over the block's lanes: float32
sum of squares, ``rsqrt(. + 1e-6)``, q scaled by ``dk ** -0.5``, one
rounding to the activations' type, which is then q or k wherever the
kernel uses them; the two backward kernels take their float32 dq and dk
through the norm's derivative before the one rounding, so what they write
is the cotangent of the conv's output.  (XLA made those values in float32
passes over (B, T, G dk), a head's sum and its way back as products with
an indicator at ``HIGHEST``, forward, recomputed and backward, and copied
v out twice a layer-step: 11.7 ms of the cell's 444 ms step, PERF.md
section 5.)  ``delta_rule_solve`` makes ``A``
and ``T = (I + A)^-1`` by substitution on the vector unit, float32
multiplies and adds (no lower a precision than products at ``HIGHEST``):
of a chunk's (C, C) matrices ``T`` alone reaches HBM, the R heads' side
by side in 128 lanes, once a step: it carries the name ``INVERSE``, which
a caller's checkpoint keeps.  ``delta_rule_fwd`` does what follows, the
chunk axis sequential and the R states float32 in a VMEM scratch from
the first chunk to the last; the decays, ``U``, ``W``, the scores and
``d`` exist only in VMEM.  The backward is written out, not autodiff:
``delta_rule_bwd`` walks the chunks from the last to the first with the
states' cotangent in scratch, makes a chunk's matrices again from q, k,
v, the decays, ``T`` and the state that entered the chunk (kept by the
differentiated forward in float32: (B, H, chunks, dk, dv), 537 MB at the
shape below, alive for one layer at a time under a block's checkpoint),
and hands ``T``'s cotangent to ``delta_rule_solve_bwd`` (``dA = -T^T dT
T^T`` below the diagonal, then k, the decays and beta).  XLA is left
with the cumulative sum of g by chunk and its reverse (1 M numbers), the
sum of k's two cotangents and, of the conv's whole output, the one pass
that lays dq | dk | dv side by side.

*Plain XLA* (``_spans_form``), for everything else: float32, the tests'
8-wide heads, other chunk sizes, the CPU; round it, for the conv's whole
output, XLA's slices and ``l2norm_heads``.  It is the definition the tests
hold to the recurrence at 1e-5, and one test holds the kernels to it.
``A``, ``T``, ``U``, ``W`` and the (C, C) scores are made for ``SPAN``
chunks at once, as batched products; only the four products that read the
entering state run chunk by chunk, in a ``lax.scan`` inside the span.  ``T`` is
made of products alone: ``A`` is nilpotent (``A^C = 0``), so ``(I + A)^-1 =
(I - A)(I + A^2)(I + A^4) ...`` ends after ``log2 C`` factors, each a
squaring and a product.  Its backward is written out (``dA = -T^T dT
T^T``): autodiff through the factors would keep every power of ``A`` of
every chunk, eleven (C, C) matrices a chunk where ``T`` alone is enough.
Everything else is plain autodiff through a ``lax.scan`` over the spans
whose body is checkpointed: the backward keeps the state entering each
span ((dk, dv) float32 a head) and ``T`` ((C, C) float32 a head and a
chunk, 134 MB a layer at the shape below: the solve is the costliest part
and is not run again; a caller's own checkpoint may keep it by the name
``INVERSE`` too) and makes the span's other matrices again, so what is
alive at once is one span's and not the sequence's (at 8,192
positions and 32 heads of 128 the sequence's ``T``, ``U``, ``W``, scores,
decays and states are 3 GB a layer, a span of 16 chunks' an eighth).

Precision.  Every exponent is <= 0 (a decay), cumulative sums, decays,
the state and all accumulation are float32, and the solve (``T`` and the
two products ``T`` is applied in, with their cotangents) multiplies
float32 operands at ``HIGHEST`` or substitutes in float32: an error in
``T`` is an error in every correction of the chunk.  The other products (k . k, q . k, and the four against the
state) take their operands in the activations' type, bf16 in a bf16
model, with float32 accumulation, as a flash kernel's scores do; with
float32 activations everything is float32 at ``HIGHEST``, which is how
the tests hold the chunked form to the recurrence at 1e-5.

A sequence that is no whole number of chunks is padded with ``g = 0``,
``beta = 0`` and zero rows: such a position leaves the state as it is.
Segment resets (packed documents) are not written.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.ad_checkpoint import checkpoint_name

_HI = lax.Precision.HIGHEST
CHUNK = 64
SPAN = 16       # chunks whose (C, C) work is made at once, and kept at once
INVERSE = "delta_rule_inverse"      # the name the solve's result is kept by


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def l2norm_heads(x: jax.Array, heads: int, eps: float = 1e-6) -> jax.Array:
    """``l2norm`` over each of ``heads`` equal runs of the last axis, the
    runs left where they lie: (..., heads d) in and out, float32.  A
    run's sum of squares, and its way back over the run's lanes, are
    products with the runs' 0/1 indicator (heads d, heads) at ``HIGHEST``
    (a product with 1 is exact), so nothing is laid out as (..., heads, d):
    on the TPU that is a float32 copy of its own each way for every use
    (1.1 ms each at 2 x 8,192 x 2,048, a dozen a layer: my chip run, PR
    58), where the projection's own (B, T, heads d) needs none."""
    x = x.astype(jnp.float32)
    n = x.shape[-1]
    runs = (jnp.arange(n)[:, None] // (n // heads)
            == jnp.arange(heads)[None, :]).astype(jnp.float32)
    squares = jnp.matmul(x * x, runs, precision=_HI)
    return x * jnp.matmul(lax.rsqrt(squares + eps), runs.T, precision=_HI)


def delta_rule_step(state: jax.Array, q: jax.Array, k: jax.Array,
                    v: jax.Array, g: jax.Array, beta: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """One token: state (..., dk, dv) float32, q, k (..., dk), v (..., dv),
    g, beta (...) -> (o (..., dv) float32, the state one token on).  The
    four lines of the module's head, in float32 at ``HIGHEST``."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    state = state * jnp.exp(g.astype(jnp.float32))[..., None, None]
    seen = jnp.einsum("...kv,...k->...v", state, k, precision=_HI)
    d = beta.astype(jnp.float32)[..., None] * (v - seen)
    state = state + k[..., :, None] * d[..., None, :]
    return jnp.einsum("...kv,...k->...v", state, q, precision=_HI), state


# ------------------------------------------------------------------ the solve
def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


@jax.custom_vjp
def inverse_unit_lower(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a`` (..., C, C) float32, strictly lower
    triangular: the product ``(I - a)(I + a^2)(I + a^4) ...`` up to the
    power that is zero, 2 (ceil(log2 C) - 1) matrix products."""
    c = a.shape[-1]
    n = -a
    t = jnp.eye(c, dtype=a.dtype) + n
    for _ in range(1, max(1, math.ceil(math.log2(c)))):
        n = _mm(n, n)
        t = t + _mm(n, t)
    return t


def _inverse_fwd(a):
    t = inverse_unit_lower(a)
    return t, t


def _inverse_bwd(t, g):
    """T = (I + A)^-1, so dT = -T dA T and the cotangent of A is
    ``-T^T G T^T``; what falls on or above the diagonal belongs to no
    entry of a strictly lower ``a`` and is masked by whoever made it."""
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(tt, _mm(g, tt)),)


inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


# -------------------------------------------------------------- chunked form
def _product(spec: str, a: jax.Array, b: jax.Array, dtype) -> jax.Array:
    """An einsum with operands of ``dtype`` and a float32 result: at
    ``HIGHEST`` for float32 operands, one bf16 pass with float32
    accumulation for bf16 ones."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=_HI if dtype == jnp.float32 else None)


def _span(state, xs, *, dtype):
    """``SPAN`` chunks at once: state (B, G, R, dk, dv) float32 entering,
    xs = (q, k (B, G, m, C, dk), v (B, G, R, m, C, dv), g, beta (B, G, R,
    m, C)) -> (the state leaving, o (B, G, R, m, C, dv))."""
    qc, kc, vc, gc, bc = xs
    C = qc.shape[-2]
    c = jnp.cumsum(gc, axis=-1)                              # (B,G,R,m,C)
    pos = jnp.arange(C)
    seen = pos[:, None] >= pos[None, :]                      # j <= i
    decay = jnp.exp(jnp.where(seen, c[..., :, None] - c[..., None, :],
                              -jnp.inf))                     # (B,G,R,m,C,C)
    kk = _product("bgnid,bgnjd->bgnij", kc, kc, dtype)
    qk = _product("bgnid,bgnjd->bgnij", qc, kc, dtype)
    a = jnp.where(pos[:, None] > pos[None, :],
                  kk[:, :, None] * bc[..., None] * decay, 0.0)
    t = checkpoint_name(inverse_unit_lower(a), INVERSE)      # (B,G,R,m,C,C)
    kc32 = kc.astype(jnp.float32)[:, :, None]                # (B,G,1,m,C,dk)
    u = _mm(t, bc[..., None] * vc.astype(jnp.float32))       # (B,G,R,m,C,dv)
    w = _mm(t, (bc * jnp.exp(c))[..., None] * kc32)          # (B,G,R,m,C,dk)
    scores = (qk[:, :, None] * decay).astype(dtype)          # (B,G,R,m,C,C)
    q_in = (jnp.exp(c)[..., None]
            * qc.astype(jnp.float32)[:, :, None]).astype(dtype)
    k_out = (jnp.exp(c[..., -1:] - c)[..., None] * kc32).astype(dtype)
    keep = jnp.exp(c[..., -1])                               # (B,G,R,m)

    def chunk_step(state, xs):
        u, w, scores, q_in, k_out, keep = xs
        low = state.astype(dtype)
        d = u - _product("bgrik,bgrkv->bgriv", w, low, dtype)
        o = _product("bgrik,bgrkv->bgriv", q_in, low, dtype) \
            + _product("bgrij,bgrjv->bgriv", scores, d, dtype)
        state = keep[..., None, None] * state \
            + _product("bgrik,bgriv->bgrkv", k_out, d, dtype)
        return state, o.astype(dtype)

    lead = lambda x: jnp.moveaxis(x, 3, 0)                   # chunks lead
    state, o = lax.scan(chunk_step, state, (
        lead(u), lead(w.astype(dtype)), lead(scores), lead(q_in),
        lead(k_out), lead(keep)))
    return state, jnp.moveaxis(o, 0, 3)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _spans_form(q, k, v, g, beta, chunk, state0):
    """``gated_delta_rule`` in plain XLA, for any shape and type: a scan
    over checkpointed spans round a scan over a span's chunks."""
    (B, T, G, dk), (H, dv) = q.shape, v.shape[2:]
    R, C = H // G, chunk
    dtype = jnp.dtype(v.dtype)
    m = min(SPAN, -(-T // C))                # chunks a span
    pad = -T % (C * m)
    S = (T + pad) // (C * m)                 # spans

    def spans(x, heads):
        """(B, T, *heads, ...) -> (S, B, *heads, m, C, ...), padded."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, S, m, C, *heads, *x.shape[3:])
        n = len(heads)
        return jnp.moveaxis(x, (1, 2, 3), (0, 2 + n, 3 + n))

    xs = (spans(q, (G,)), spans(k, (G,)), spans(v, (G, R)),
          spans(g.astype(jnp.float32), (G, R)),
          spans(beta.astype(jnp.float32), (G, R)))
    state, o = lax.scan(
        jax.checkpoint(functools.partial(_span, dtype=dtype),
                       policy=jax.checkpoint_policies.save_only_these_names(
                           INVERSE)),
        state0.astype(jnp.float32).reshape(B, G, R, dk, dv), xs)
    # (S, B, G, R, m, C, dv) -> (B, T, H, dv)
    o = o.transpose(1, 0, 4, 5, 2, 3, 6).reshape(B, S * m * C, H, dv)[:, :T]
    return o, state.reshape(B, H, dk, dv)


# ------------------------------------------------------------------- kernels
STEP_CHUNKS = 4     # chunks a grid step of a kernel works through


def _dot(a, b, contract, precision=None):
    """A product with a float32 result, contracting axis ``contract[0]``
    of ``a`` with ``contract[1]`` of ``b``."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _low(dtype):
    """(cast to the products' operand type, their precision): one bf16
    pass for bf16 activations; float32 ones (the tests') at ``HIGHEST``."""
    return (lambda x: x.astype(dtype)), \
        (_HI if dtype == jnp.float32 else None)


def _column(row, eye):
    """(1, C) -> (C, 1), exactly: the diagonal of the row laid over (C, C),
    summed along the lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """(C, 1) -> (1, C), exactly."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _positions(C):
    """(C, C) positions i (down) and j (across)."""
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _decays(rows_ref, ci, r, R, i, j):
    """Chunk ``ci``, value head ``r``: beta and the cumulative log-decay c
    as columns (C, 1), c as a row, and c_i - c_j (C, C)."""
    eye = i == j
    c_row = rows_ref[ci, pl.ds(r, 1), :]                      # (1, C)
    b_col = _column(rows_ref[ci, pl.ds(R + r, 1), :], eye)
    c_col = _column(c_row, eye)
    return b_col, c_col, c_row, c_col - c_row


def _head_parts(rows_ref, t_ref, v_ref, k32, ci, r, R, C, dv, i, j):
    """Chunk ``ci``, value head ``r``: the decays by row and by column, the
    (C, C) decay ``exp(c_i - c_j)`` (j <= i, else 0), and ``T [beta v,
    beta exp(c) k]`` (C, dv + dk) at the solve's precision."""
    b_col, c_col, c_row, diff = _decays(rows_ref, ci, r, R, i, j)
    last = jnp.sum(jnp.where(j[:1] == C - 1, c_row, 0.0), axis=1,
                   keepdims=True)                             # (1, 1)
    decay = jnp.where(j <= i, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    e_col, f_col = jnp.exp(c_col), jnp.exp(last - c_col)
    keep = jnp.broadcast_to(jnp.exp(last), (1, dv))   # Mosaic broadcasts
    #                                     one axis of a (1, 1) at a time
    v32 = v_ref[pl.ds(ci * C, C), pl.ds(r * dv, dv)].astype(jnp.float32)
    t = t_ref[ci, :, pl.ds(r * C, C)]                         # (C, C)
    rhs = jnp.concatenate([b_col * v32, (b_col * e_col) * k32], axis=1)
    uw = _dot(t, rhs, (1, 0), _HI)
    return dict(b_col=b_col, diff=diff, decay=decay, e_col=e_col,
                f_col=f_col, keep=keep, v32=v32, t=t, rhs=rhs,
                u=uw[:, :dv], w=uw[:, dv:])


_EPS = 1e-6         # ``l2norm``'s


def _unit(x):
    """A chunk of a key head (C, dk) -> (its rows at length 1 but for
    ``_EPS``, float32; the rows' ``rsqrt`` (C, 1)): ``l2norm``, the sum
    of squares a reduce over the head's lanes."""
    x32 = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.sum(x32 * x32, axis=1, keepdims=True) + _EPS)
    return x32 * r, r


def _normed(x, norm, scale=1.0):
    """A chunk of a key head, (C, dk) as loaded -> as the products take it.
    ``norm`` False: the caller normed it.  True: it is the conv's output,
    and what ``l2norm_heads`` and the model's cast made of it is made
    here: float32 sum of squares over the head's lanes, ``rsqrt``, the
    queries' ``scale``, one rounding to the activations' type."""
    if not norm:
        return x
    n, _ = _unit(x)
    return (n if scale == 1.0 else n * scale).astype(x.dtype)


def _through_norm(dy, x, norm, scale=1.0):
    """The float32 cotangent ``dy`` of ``_normed(x)`` -> that of ``x``:
    ``scale r (dy - n sum(n dy))`` a row with ``n = x r``, the rounding's
    derivative the identity (as ``astype``'s is).  ``n`` and ``r`` are
    made again from the block, which is in VMEM: kept since the chunk's
    head they would be live across all its products."""
    if not norm:
        return dy
    n, r = _unit(x)
    return (r if scale == 1.0 else r * scale) \
        * (dy - n * jnp.sum(n * dy, axis=1, keepdims=True))


def _fwd_kernel(rows_ref, q_ref, k_ref, v_ref, t_ref, s0_ref, o_ref, sn_ref,
                states_ref, s_scr, *, R, m, C, norm):
    """Grid (batch x key head, step): ``m`` chunks of the R value heads
    that read one key head; ``s_scr`` carries the R states from step to
    step, ``states_ref`` keeps the state that entered each chunk."""
    step = pl.program_id(1)
    dv = v_ref.shape[-1] // R
    low, prec = _low(q_ref.dtype)
    q_scale = q_ref.shape[-1] ** -0.5

    @pl.when(step == 0)
    def _():
        s_scr[...] = s0_ref[...]

    i, j = _positions(C)
    for ci in range(m):
        at = pl.ds(ci * C, C)
        q = _normed(q_ref[at, :], norm, q_scale)
        k = _normed(k_ref[at, :], norm)
        q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
        qk = _dot(q, k, (1, 1), prec)
        for r in range(R):
            p = _head_parts(rows_ref, t_ref, v_ref, k32, ci, r, R, C, dv,
                            i, j)
            state = s_scr[r]
            states_ref[ci, r] = state
            s_low = low(state)
            d = low(p["u"] - _dot(low(p["w"]), s_low, (1, 0), prec))
            o = _dot(low(p["e_col"] * q32), s_low, (1, 0), prec) \
                + _dot(low(qk * p["decay"]), d, (1, 0), prec)
            o_ref[at, pl.ds(r * dv, dv)] = o.astype(o_ref.dtype)
            s_scr[r] = p["keep"] * state \
                + _dot(low(p["f_col"] * k32), d, (0, 0), prec)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        sn_ref[...] = s_scr[...]


def _bwd_kernel(rows_ref, q_ref, k_ref, v_ref, t_ref, states_ref, do_ref,
                dsn_ref, dq_ref, dk_ref, dv_ref, drows_ref, dt_ref, ds0_ref,
                ds_scr, *, R, m, C, norm):
    """The forward's grid with the steps, and the chunks of a step, in
    reverse: ``ds_scr`` carries the cotangent of the R states from the
    last chunk to the first.  A chunk's matrices are made again from q,
    k, v, the decays, ``T`` and the state that entered it."""
    step = pl.program_id(1)
    dv = v_ref.shape[-1] // R
    low, prec = _low(q_ref.dtype)
    q_scale = q_ref.shape[-1] ** -0.5

    @pl.when(step == 0)
    def _():
        ds_scr[...] = dsn_ref[...]

    i, j = _positions(C)
    eye = i == j
    for ci in reversed(range(m)):
        at = pl.ds(ci * C, C)
        q = _normed(q_ref[at, :], norm, q_scale)
        k = _normed(k_ref[at, :], norm)
        q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
        qk = _dot(q, k, (1, 1), prec)
        kq = _dot(k, q, (1, 1), prec)                    # qk's transpose
        dq = jnp.zeros(q32.shape, jnp.float32)
        dk = jnp.zeros(k32.shape, jnp.float32)
        dqk = jnp.zeros((C, C), jnp.float32)
        for r in range(R):
            p = _head_parts(rows_ref, t_ref, v_ref, k32, ci, r, R, C, dv,
                            i, j)
            e_col, f_col, b_col, keep = (p[n] for n in (
                "e_col", "f_col", "b_col", "keep"))
            state = states_ref[ci, r]
            s_low, w_low = low(state), low(p["w"])
            d = low(p["u"] - _dot(w_low, s_low, (1, 0), prec))
            scores = qk * p["decay"]
            decay_t = jnp.where(i <= j, jnp.exp(jnp.minimum(-p["diff"], 0.0)),
                                0.0)
            q_in, k_out = low(e_col * q32), low(f_col * k32)
            do = do_ref[at, pl.ds(r * dv, dv)]
            ds = ds_scr[r]
            ds_low = low(ds)
            # the cotangents of d, of the (C, C) scores, and of the three
            # operands that met the state
            dd = _dot(low(kq * decay_t), do, (1, 0), prec) \
                + _dot(k_out, ds_low, (1, 0), prec)
            dd_low = low(dd)
            dscores = _dot(do, d, (1, 1), prec)
            dq_in = _dot(do, s_low, (1, 1), prec)
            dw = -_dot(dd_low, s_low, (1, 1), prec)
            dk_out = _dot(d, ds_low, (1, 1), prec)
            dkeep = jnp.sum(jnp.sum(state * ds, axis=1, keepdims=True),
                            axis=0, keepdims=True)               # (1, 1)
            ds_scr[r] = keep * ds + _dot(
                jnp.concatenate([q_in, -w_low], axis=0),
                jnp.concatenate([do, dd_low], axis=0), (0, 0), prec)
            # through T, at the solve's precision
            duw = jnp.concatenate([dd, dw], axis=1)              # (C, dv+dk)
            dt = _dot(duw, p["rhs"], (1, 1), _HI)
            drhs = _dot(p["t"], duw, (0, 0), _HI)
            dvb, dkb = drhs[:, :dv], drhs[:, dv:]
            dv_ref[at, pl.ds(r * dv, dv)] = (b_col * dvb).astype(dv_ref.dtype)
            from_kb = jnp.sum(dkb * k32, axis=1, keepdims=True)
            from_k_out = f_col * jnp.sum(dk_out * k32, axis=1, keepdims=True)
            dbeta = jnp.sum(dvb * p["v32"], axis=1, keepdims=True) \
                + e_col * from_kb
            dk = dk + (b_col * e_col) * dkb + f_col * dk_out
            dq = dq + e_col * dq_in
            through = dscores * scores          # d decay x decay, below
            dc = (b_col * e_col) * from_kb - from_k_out \
                + e_col * jnp.sum(dq_in * q32, axis=1, keepdims=True) \
                + jnp.sum(through, axis=1, keepdims=True)
            dlast = keep[:, :1] * dkeep + jnp.sum(from_k_out, axis=0, keepdims=True)
            dc_row = _row(dc, eye) - jnp.sum(through, axis=0, keepdims=True) \
                + jnp.where(j[:1] == C - 1, dlast, 0.0)
            drows_ref[ci, pl.ds(r, 1), :] = dc_row
            drows_ref[ci, pl.ds(R + r, 1), :] = _row(dbeta, eye)
            dt_ref[ci, :, pl.ds(r * C, C)] = dt
            dqk = dqk + dscores * p["decay"]
        dqk_low = low(dqk)
        dq_ref[at, :] = _through_norm(
            dq + _dot(dqk_low, k, (1, 0), prec), q_ref[at, :], norm,
            q_scale).astype(dq_ref.dtype)
        dk_ref[at, :] = _through_norm(
            dk + _dot(dqk_low, q, (0, 0), prec), k_ref[at, :], norm
            ).astype(dk_ref.dtype)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        ds0_ref[...] = ds_scr[...]


def _below(i, j, diff):
    """exp(c_i - c_j) strictly below the diagonal, else 0."""
    return jnp.where(j < i, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)


def _inverses_by_substitution(a):
    """``(I + a)^-1`` for each strictly lower triangular (C, C) float32
    matrix of ``a`` (S, C, C), by substitution: row i of an inverse is e_i
    less the earlier rows times ``a``'s row i, so once row j stands,
    ``a``'s column j times it goes off every row below.  Float32
    multiplies and adds on the vector unit, a tile of 8 rows set aside as
    it is finished (it has nothing more to take), the S systems in one array and so
    step for step: one alone waits on its own row j 63 times over (my chip
    run, PR 58: 7.0 ms a layer one after another, 4.9 eight in step), and
    a step traced once for all of them is an eighth of the operations to
    trace each time the step is built."""
    S, C, _ = a.shape
    pos = lax.broadcasted_iota(jnp.int32, (S, C, C), 2) \
        - lax.broadcasted_iota(jnp.int32, (S, C, C), 1)
    below = (pos == 0).astype(jnp.float32)      # the rows not yet finished
    done = []
    for j in range(C - 1):
        if j % 8 == 0 and j:                    # a tile of 8 rows stands
            done.append(below[:, :8])
            below, a = below[:, 8:], a[:, 8:]
        below = below - a[:, :, j:j + 1] * below[:, j % 8:j % 8 + 1]
    return jnp.concatenate(done + [below], axis=1)


def _solve_kernel(rows_ref, k_ref, t_ref, *, R, m, C, norm):
    """Grid (batch x key head, step): the solved systems ``T = (I + A)^-1``
    of ``m`` chunks' R value heads, the heads' side by side."""
    _, prec = _low(k_ref.dtype)
    i, j = _positions(C)
    systems = []
    for ci in range(m):
        k = _normed(k_ref[pl.ds(ci * C, C), :], norm)
        kk = _dot(k, k, (1, 1), prec)
        for r in range(R):
            b_col, _, _, diff = _decays(rows_ref, ci, r, R, i, j)
            systems.append(b_col * (kk * _below(i, j, diff)))
    t = _inverses_by_substitution(jnp.stack(systems))
    for at in range(m * R):
        t_ref[at // R, :, pl.ds(at % R * C, C)] = t[at]


def _solve_bwd_kernel(rows_ref, k_ref, t_ref, dt_ref, dk_ref, drows_ref, *,
                      R, m, C, norm):
    """``_solve_kernel``'s backward: ``dA = -T^T dT T^T`` below the
    diagonal, and from it the cotangents of k (through k . k, summed over
    the R value heads), of the log-decays and of beta."""
    low, prec = _low(k_ref.dtype)
    i, j = _positions(C)
    eye = i == j
    for ci in range(m):
        at = pl.ds(ci * C, C)
        k = _normed(k_ref[at, :], norm)
        kk = _dot(k, k, (1, 1), prec)
        dkk = jnp.zeros((C, C), jnp.float32)
        for r in range(R):
            b_col, _, _, diff = _decays(rows_ref, ci, r, R, i, j)
            decay = _below(i, j, diff)
            t = t_ref[ci, :, pl.ds(r * C, C)]
            da = -_dot(t, _dot(dt_ref[ci, :, pl.ds(r * C, C)], t, (1, 1),
                               _HI), (0, 0), _HI)
            below = kk * decay
            scaled = b_col * da * decay            # zero on and above
            through = scaled * kk
            drows_ref[ci, pl.ds(r, 1), :] = _row(
                jnp.sum(through, axis=1, keepdims=True), eye) \
                - jnp.sum(through, axis=0, keepdims=True)
            drows_ref[ci, pl.ds(R + r, 1), :] = _row(
                jnp.sum(da * below, axis=1, keepdims=True), eye)
            dkk = dkk + scaled
        dkk = low(dkk)
        dk_ref[at, :] = _through_norm(
            _dot(dkk, k, (1, 0), prec) + _dot(dkk, k, (0, 0), prec),
            k_ref[at, :], norm).astype(dk_ref.dtype)


# name -> (body, operands, results, steps from the last to the first, a
# state carried from step to step in scratch), operands and results by the
# names of ``_kernel``'s specs
_KERNELS = {
    "delta_rule_solve": (_solve_kernel, ("rows", "k"), ("t",), False, False),
    "delta_rule_fwd": (_fwd_kernel, ("rows", "q", "k", "v", "t", "state"),
                       ("o", "state", "states"), False, True),
    "delta_rule_bwd": (_bwd_kernel, ("rows", "q", "k", "v", "t", "states",
                                     "o", "state"),
                       ("dqk", "dqk", "o", "rows", "t", "state"), True, True),
    "delta_rule_solve_bwd": (_solve_bwd_kernel, ("rows", "k", "t", "t"),
                             ("dqk", "rows"), False, False),
}


@functools.lru_cache(maxsize=None)
def _kernel(name, B, G, N, R, C, dk, dv, dtype, m, whole, interpret):
    """One of the four kernels at one shape: grid (batch x key head ``bg``,
    step ``n`` of ``m`` chunks), q, k, v as (B, T, heads x width) column
    blocks: of three arrays the caller normed, or (``whole``) of the
    conv's one unnormed (B, T, q | k | v), k's blocks behind the G of q,
    v's behind both, which the body then norms.  The ``pallas_call``
    stands inside a jitted function of the
    kernel's name: in the Qwen3-Next step the v5e names a custom call
    after the function it stands in (``gated_delta_rule.76``) and,
    standing in none, ``tpu_custom_call.149`` (my chip runs, PR 58), the
    name ``kernels.custom_call_ms`` reads the flash kernels by.  Kept, so
    that building a step traces a kernel's body once however often
    ``custom_vjp`` and a checkpoint ask for it (a step's ``setup_s``)."""
    from jax.experimental.pallas import tpu as pltpu
    body, operands, results, back, carried = _KERNELS[name]
    steps = N // m
    at = (lambda n: steps - 1 - n) if back else (lambda n: n)

    def by_time(width, first=0, lanes=None):
        """(B, T, ``lanes``), a key head's ``width`` lanes a block, head
        0's the block ``first``."""
        return (jax.ShapeDtypeStruct((B, N * C, lanes or G * width), dtype),
                pl.BlockSpec((None, m * C, width),
                             lambda bg, n: (bg // G, at(n), first + bg % G)))
    by_chunk = lambda *shape: (
        jax.ShapeDtypeStruct((B * G, N, *shape), jnp.float32),
        pl.BlockSpec((None, m, *shape),
                     lambda bg, n: (bg, at(n)) + (0,) * len(shape)))
    # three arrays, or the one's lanes and head 0's block of q, k and v
    lanes = G * (2 * dk + R * dv) if whole else None
    q0, k0, v0 = (0, G, 2 * G * dk // (R * dv)) if whole else (0, 0, 0)
    of = dict(q=by_time(dk, q0, lanes), k=by_time(dk, k0, lanes),
              v=by_time(R * dv, v0, lanes), dqk=by_time(dk), o=by_time(R * dv), rows=by_chunk(2 * R, C),
              t=by_chunk(C, R * C), states=by_chunk(R, dk, dv),
              state=(jax.ShapeDtypeStruct((B * G, R, dk, dv), jnp.float32),
                     pl.BlockSpec((None, R, dk, dv),
                                  lambda bg, n: (bg, 0, 0, 0))))

    def run(*args):
        return pl.pallas_call(
            functools.partial(body, R=R, m=m, C=C, norm=whole),
            grid=(B * G, steps),
            in_specs=[of[x][1] for x in operands],
            out_specs=[of[x][1] for x in results],
            out_shape=[of[x][0] for x in results],
            scratch_shapes=[pltpu.VMEM((R, dk, dv), jnp.float32)] * carried,
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "arbitrary" if carried else "parallel")),
            interpret=interpret, name=name)(*args)
    run.__name__ = run.__qualname__ = name
    return jax.jit(run)


def _call(name, rows, x, G, dk, interpret, *args):
    """Kernel ``name`` at the shape of rows (B G, N, 2 R, C) and ``x``,
    ``_chunks``'s, on ``args``."""
    BG, N, R2, C = rows.shape
    R = R2 // 2
    dv = (sum(a.shape[-1] for a in x) - 2 * G * dk) // (G * R)
    return _kernel(name, BG // G, G, N, R, C, dk, dv, jnp.dtype(x[0].dtype),
                   min(STEP_CHUNKS, N), len(x) == 1, interpret)(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _chunks(rows, x, state0, G, dk, interpret):
    """The chunks' work in kernels: rows (B G, N, 2 R, C) float32, a
    chunk's cumulative log-decays of the R value heads of a key head and
    then their betas; ``x`` either (q, k (B, T, G dk), v (B, T, H dv)),
    normed by the caller, or (qkv (B, T, 2 G dk + H dv),), the conv's
    output as it lies, which the kernels norm; state0 (B G, R, dk, dv)
    float32 -> (o like v, the last state like state0)."""
    return _chunks_fwd(rows, x, state0, G, dk, interpret)[0]


def _chunks_fwd(rows, x, state0, G, dk, interpret):
    """``delta_rule_solve`` makes ``T = (I + A)^-1`` (B G, N, C, R C)
    float32, ``A_ij = beta_i (k_i . k_j) exp(c_i - c_j)`` below the
    diagonal, so that of a chunk's (C, C) matrices only ``T`` reaches HBM;
    it is named here, so that what a caller's checkpoint keeps is the
    value the backward reads too, and the kernel is not run again for it.
    ``delta_rule_fwd`` does what follows and writes the state entering
    every chunk too (B G, N, R, dk, dv) float32, which the backward
    reads: one kernel either way, so that a step is built from one trace
    of it (a call that is not differentiated writes them and drops them:
    0.2 ms a layer at the cell's shape)."""
    q, k, v = x if len(x) == 3 else x * 3
    t = checkpoint_name(
        _call("delta_rule_solve", rows, x, G, dk, interpret, rows, k)[0],
        INVERSE)
    o, state, states = _call("delta_rule_fwd", rows, x, G, dk, interpret,
                             rows, q, k, v, t, state0)
    return (o, state), (rows, x, t, states)


def _chunks_bwd(G, dk, interpret, res, cts):
    """The two written-out backwards; k's cotangent is the sum of both,
    and the conv's output's is the three side by side, in one pass."""
    rows, x, t, states = res
    q, k, v = x if len(x) == 3 else x * 3
    do, dsn = cts
    dq, dk_rule, dv, drows, dt, ds0 = _call(
        "delta_rule_bwd", rows, x, G, dk, interpret, rows, q, k, v, t,
        states, do, dsn.astype(jnp.float32))
    dk_solve, drows_solve = _call("delta_rule_solve_bwd", rows, x, G, dk,
                                  interpret, rows, k, t, dt)
    if len(x) == 3:
        return drows + drows_solve, (dq, dk_rule + dk_solve, dv), ds0
    # each where it lies in a zero (B, T, W) and the four summed: XLA makes
    # one fusion of it (a concatenate left k's sum a pass of its own)
    kw, W = dq.shape[-1], x[0].shape[-1]
    dx = sum(jnp.pad(part, ((0, 0), (0, 0), (at, W - at - part.shape[-1])))
             for at, part in ((0, dq), (kw, dk_rule), (kw, dk_solve),
                              (2 * kw, dv)))
    return drows + drows_solve, (dx,), ds0


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _in_kernels(x, g, beta, state0, G, dk, chunk, interpret):
    """``_chunks`` on a call's arrays: ``x`` as it takes them, g, beta (B,
    T, H), state0 (B, H, dk, dv) -> (o (B, T, H, dv), the last state).
    XLA makes the cumulative log-decays a chunk."""
    (B, T), H, C = g.shape[:2], g.shape[2], chunk
    R = H // G
    n = -(-T // C)
    m = min(STEP_CHUNKS, n)
    pad = -T % (C * m)
    N = (T + pad) // C

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    def by_head(x):
        """(B, T, H) -> (B, G, N, R, C) float32."""
        x = padded(x.astype(jnp.float32)).reshape(B, N, C, G, R)
        return x.transpose(0, 3, 1, 4, 2)
    rows = jnp.concatenate([jnp.cumsum(by_head(g), axis=-1), by_head(beta)],
                           axis=3).reshape(B * G, N, 2 * R, C)
    o, state = _chunks(
        rows, tuple(padded(a) for a in x),
        state0.astype(jnp.float32).reshape(B * G, R, dk, -1), G, dk,
        interpret)
    return o.reshape(B, N * C, H, -1)[:, :T], state.reshape(state0.shape)


def delta_rule_chunks(q, k, v, g, beta, state0, *, chunk: int = CHUNK,
                      interpret: bool = False):
    """``gated_delta_rule`` with the chunks' work in Pallas kernels: same
    arguments and results.  One kernel makes the solved systems ``T``
    (kept across a caller's checkpoint by the name ``INVERSE``: the solve
    is not run again), another everything that follows; each has its
    backward written out as a kernel."""
    (B, T, G, dk) = q.shape
    return _in_kernels(tuple(a.reshape(B, T, -1) for a in (q, k, v)), g,
                       beta, state0, G, dk, chunk, interpret)


def delta_rule_chunks_qkv(qkv, g, beta, state0, key_heads: int, key_dim: int,
                          *, chunk: int = CHUNK, interpret: bool = False):
    """``gated_delta_rule_qkv`` in the same kernels, which read q, k and v
    out of ``qkv`` where they lie and norm the q and k they have loaded."""
    return _in_kernels((qkv,), g, beta, state0, key_heads, key_dim, chunk,
                       interpret)


def _kernels_run(dtype, G: int, dk: int, H: int, dv: int, chunk: int,
                 whole: bool = False) -> bool:
    """Whether a call's chunks run in the kernels: on a TPU, bf16
    activations, chunks of 64, heads of whole 128-lane blocks and whole
    groups of value heads a key head; of the conv's ``whole`` output, a
    group's values in whole blocks behind q and k.  Everything else
    (float32, the tests' 8-wide heads, other chunk sizes, the CPU) takes
    the XLA form."""
    return (jax.default_backend() == "tpu" and chunk == CHUNK
            and dtype == jnp.bfloat16
            and dk % 128 == 0 and dv % 128 == 0 and H % G == 0
            and not (whole and 2 * G * dk % (H // G * dv)))


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK,
                     state0: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence of :func:`delta_rule_step` over a sequence.

    q, k (B, T, G, dk), the keys l2-normed and the queries scaled by the
    caller; v (B, T, H, dv) with H = G R: value head h reads key head
    h // R (no repeated copy of q or k is made); g, beta (B, T, H);
    state0 (B, H, dk, dv) float32 or None for zeros.  Returns (o (B, T, H,
    dv) in v's type, the state after the last position (B, H, dk, dv)
    float32).  Products that are no part of the solve take operands of
    v's type (the module's head).  Which form runs is read from the call
    (``_kernels_run``): both are the same chunked algorithm at the same
    precisions."""
    (B, _, G, dk), (H, dv) = q.shape, v.shape[2:]
    if state0 is None:
        state0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    if q.dtype == v.dtype and _kernels_run(v.dtype, G, dk, H, dv, chunk):
        return delta_rule_chunks(q, k, v, g, beta, state0, chunk=chunk)
    return _spans_form(q, k, v, g, beta, chunk, state0)


def gated_delta_rule_qkv(qkv: jax.Array, g: jax.Array, beta: jax.Array,
                         key_heads: int, key_dim: int, chunk: int = CHUNK,
                         state0: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """:func:`gated_delta_rule` of a DeltaNet mixer's conv output, whole
    and unnormed: qkv (B, T, 2 G dk + H dv), the G query heads, the G key
    heads and the H value heads side by side; q and k are l2-normed a
    head (``l2norm_heads``: float32, eps 1e-6), q scaled by ``dk ** -0.5``,
    both rounded once to qkv's type; g, beta, state0 and the results as
    there.  Where the kernels run they read the three out of ``qkv`` by
    column blocks and norm the blocks they have loaded, so no slice and
    no normed copy is made, and the backward writes the cotangent of
    ``qkv``; elsewhere the slices and the norms are XLA's, round the XLA
    form."""
    (B, _, W), H, G, dk = qkv.shape, g.shape[-1], key_heads, key_dim
    dv = (W - 2 * G * dk) // H
    if state0 is None:
        state0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    if _kernels_run(qkv.dtype, G, dk, H, dv, chunk, whole=True):
        return delta_rule_chunks_qkv(qkv, g, beta, state0, G, dk,
                                     chunk=chunk)
    return _spans_form(*apart(qkv, G, dk, H), g, beta, chunk, state0)


def apart(qkv: jax.Array, key_heads: int, key_dim: int, value_heads: int
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A conv's q | k | v (B, T, 2 G dk + H dv) -> what
    :func:`gated_delta_rule` takes: q, k (B, T, G, dk) l2-normed a head,
    q scaled by ``dk ** -0.5``, both rounded once to qkv's type, and v
    (B, T, H, dv): XLA's slices and norms, which the kernels spare."""
    (B, T, _), G, dk, kw = qkv.shape, key_heads, key_dim, key_heads * key_dim
    q = l2norm_heads(qkv[..., :kw], G) * dk ** -0.5
    k = l2norm_heads(qkv[..., kw:2 * kw], G)
    return (q.astype(qkv.dtype).reshape(B, T, G, dk),
            k.astype(qkv.dtype).reshape(B, T, G, dk),
            qkv[..., 2 * kw:].reshape(B, T, value_heads, -1))


# ------------------------------------------- the per-channel gate (Kimi KDA)
# Kimi Delta Attention: the same rule with a decay a CHANNEL of the key,
# ``S <- diag(exp(g_t)) S`` with ``g_t`` (dk,), -5 < g < 0 (the model's
# ``kda_safe_gate``).  The decay then does not factor out of ``k_i . k_j``
# as a head's one number does: ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic -
# G_jc)`` with ``G`` the running sum of g inside the chunk.  The products
# are formed from ``a_i exp(G_i - G_ref)`` and ``b_j exp(G_ref - G_j)`` in
# float32 with ``G_ref`` at the first position of the ROW's block of
# ``KDA_BLOCK`` positions: for the columns of earlier blocks both
# exponents are <= 0, for the row's own block the second is at most
# (KDA_BLOCK - 1) x 5 = 75 < 88, where float32 (and bf16, whose exponent
# is float32's) still holds it; what lies past the diagonal is masked and
# its exponent held at that bound.  Plain XLA, forward only (the family is
# served, not trained: ROADMAP, Reach), float32 and bf16 operands as in
# ``_span``.  The scalar-gated forms above are untouched.
KDA_BLOCK = 16
_KDA_MAX_EXPONENT = 80.0


def kda_step(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token: state (..., dk, dv) float32, q, k, g (..., dk), v (...,
    dv), beta (...) -> (o (..., dv) float32, the state one token on).
    ``o = S^T q`` is taken from the decayed state and the correction, ``S1^T
    q + (k . q) d``, so that the state is read once for both products."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    state = state * jnp.exp(g.astype(jnp.float32))[..., :, None]
    seen = jnp.einsum("...kv,...nk->...nv", state, jnp.stack([k, q], -2),
                      precision=_HI)
    d = beta.astype(jnp.float32)[..., None] * (v - seen[..., 0, :])
    o = seen[..., 1, :] + (k * q).sum(-1, keepdims=True) * d
    return o, state + k[..., :, None] * d[..., None, :]


@functools.partial(jax.jit, static_argnames=("chunk",))
def kda_chunks(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, state0: jax.Array, chunk: int = CHUNK
               ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence of :func:`kda_step` over a sequence, in chunks.

    q, k (B, T, H, dk), the keys l2-normed and the queries scaled by the
    caller; v (B, T, H, dv); g (B, T, H, dk) with ``g >= -80 / (KDA_BLOCK -
    1)``; beta (B, T, H); state0 (B, H, dk, dv) float32.  Returns (o (B, T,
    H, dv) in v's type, the state after the last position).  A position
    with ``g = 0`` and ``beta = 0`` leaves the state as it is (padding)."""
    (B, T, H, dk), dv = q.shape, v.shape[-1]
    C, blk = chunk, min(KDA_BLOCK, chunk)
    nb, dtype, f32 = C // blk, jnp.dtype(v.dtype), jnp.float32
    pad = -T % C
    n = (T + pad) // C

    def chunks(x):
        """(B, T, H, ...) -> (B, H, n, C, ...), padded with zeros."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(B, n, C, H, *x.shape[3:]), 3, 1)

    q32, k32 = chunks(q).astype(f32), chunks(k).astype(f32)
    v32, bc = chunks(v).astype(f32), chunks(beta.astype(f32))
    G = jnp.cumsum(chunks(g.astype(f32)), axis=3)            # (B,H,n,C,dk)
    ref = G[:, :, :, ::blk]                                  # (B,H,n,nb,dk)
    # a row against its block's reference, a column against each row
    # block's: exponents <= 0 and <= _KDA_MAX_EXPONENT
    down = jnp.exp(G.reshape(B, H, n, nb, blk, dk) - ref[..., None, :])
    up = jnp.exp(jnp.minimum(ref[..., None, :] - G[:, :, :, None],
                             _KDA_MAX_EXPONENT))             # (B,H,n,nb,C,dk)
    k_cols = k32[:, :, :, None] * up
    rows = lambda x: x.reshape(B, H, n, nb, blk, dk) * down  # noqa: E731
    kk = jnp.einsum("bhnrid,bhnrjd->bhnrij", rows(k32), k_cols,
                    precision=_HI).reshape(B, H, n, C, C)
    qk = _product("bhnrid,bhnrjd->bhnrij", rows(q32), k_cols,
                  dtype).reshape(B, H, n, C, C)
    pos = jnp.arange(C)
    t = inverse_unit_lower(jnp.where(pos[:, None] > pos[None, :],
                                     kk * bc[..., None], 0.0))
    into = jnp.exp(G)                                        # from S0 on
    u = _mm(t, bc[..., None] * v32)                          # (B,H,n,C,dv)
    w = _mm(t, bc[..., None] * into * k32)                   # (B,H,n,C,dk)
    scores = jnp.where(pos[:, None] >= pos[None, :], qk, 0.0).astype(dtype)
    q_in = (into * q32).astype(dtype)
    k_out = (jnp.exp(G[:, :, :, -1:] - G) * k32).astype(dtype)
    keep = jnp.exp(G[:, :, :, -1])                           # (B,H,n,dk)

    def chunk_step(state, xs):
        u, w, scores, q_in, k_out, keep = xs
        low = state.astype(dtype)
        d = u - _product("bhik,bhkv->bhiv", w, low, dtype)
        o = _product("bhik,bhkv->bhiv", q_in, low, dtype) \
            + _product("bhij,bhjv->bhiv", scores, d, dtype)
        state = keep[..., None] * state \
            + _product("bhik,bhiv->bhkv", k_out, d, dtype)
        return state, o.astype(dtype)

    lead = lambda x: jnp.moveaxis(x, 2, 0)                   # noqa: E731
    state, o = lax.scan(chunk_step, state0.astype(f32), (
        lead(u), lead(w.astype(dtype)), lead(scores), lead(q_in),
        lead(k_out), lead(keep)))
    # (n, B, H, C, dv) -> (B, T, H, dv)
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, n * C, H, dv)[:, :T]
    return o, state


# ---------------------------------------- a decode step's rows, in one pass
# A decode step of a served model steps B of a store's R rows of state,
# ``store`` (layers, R, H, dk, dv) float32, 2 MB a row at 32 heads of 128.
# Gathered, stepped and scattered by XLA that is three passes over the
# rows and two copies of them (0.82 GB a layer step at 64 rows: 2.3 ms
# where the bytes are 0.33: my chip run, PR 59).  ``kda_step_rows`` does
# it in place on a TPU: a Pallas kernel whose grid walks the batch rows,
# reads a row's heads where they lie (the row named by a prefetched
# scalar), steps them on the vector unit and writes them back to the same
# place, the store aliased to the result.  Everything a head's step needs
# besides its state is made by XLA from the (B, H, .) operands (a few MB):
# the four vectors that scale the state's ROWS (exp(g), k, k exp(g), q
# exp(g)) as columns, (B, dk, 4 H) with a (kind, head) a lane (128 lanes at
# 32 heads: laid out eight heads a block the lanes were 8 of 128 and the
# array sixteen times its size), and the three that scale its COLUMNS
# (beta v, beta, q . k) as rows (B, 3, H, dv), so the kernel transposes
# nothing.  Elsewhere (the CPU, other shapes) it is the gather,
# ``kda_step`` and the scatter.
_ROW_BYTES = 4 << 20    # the most one row's heads may take of VMEM, each way


def _kda_rows_kernel(rows_ref, layer_ref, c_ref, r_ref, s_ref, o_ref, s_out,
                     *, heads, n_rows):
    """One batch row: c_ref (1, dk, 4 heads) the columns, lane ``i heads +
    h`` kind i of head h; r_ref (1, 3, heads, dv) the rows; s_ref / s_out
    (1, 1, heads, dk, dv) the row's states; o_ref (1, heads, dv).  A batch
    row that names no row of the store (padding up to the bucket) reads the
    store's last row and writes it back as it is."""
    del layer_ref
    live = rows_ref[pl.program_id(0)] < n_rows

    @pl.when(live)
    def _():
        for h in range(heads):
            state = s_ref[0, 0, h]                              # (dk, dv)
            col = lambda i: c_ref[                              # noqa: E731
                0, :, i * heads + h:i * heads + h + 1]          # (dk, 1)
            row = lambda i: r_ref[0, i, h:h + 1, :]             # noqa: E731
            seen = jnp.sum(state * col(2), axis=0, keepdims=True)
            read = jnp.sum(state * col(3), axis=0, keepdims=True)
            d = row(0) - row(1) * seen                          # (1, dv)
            o_ref[0, h:h + 1, :] = read + row(2) * d
            s_out[0, 0, h] = state * col(0) + col(1) * d

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _kda_rows_call(store, layer, rows, q, k, v, g, beta, *, interpret=False):
    from jax.experimental.pallas import tpu as pltpu

    n_layer, n_rows, H, dk, dv = store.shape
    B, f32 = q.shape[0], jnp.float32
    q, k, v = (a.astype(f32) for a in (q, k, v))
    decay = jnp.exp(g.astype(f32))
    beta = beta.astype(f32)[..., None]
    # (B, 4, H, dk) -> (B, dk, 4 H): a (kind, head) a lane
    cols = jnp.stack([decay, k, k * decay, q * decay], axis=1)
    cols = cols.reshape(B, 4 * H, dk).transpose(0, 2, 1)
    vecs = jnp.stack([beta * v, jnp.broadcast_to(beta, v.shape),
                      jnp.broadcast_to((q * k).sum(-1, keepdims=True),
                                       v.shape)], axis=1)       # (B,3,H,dv)

    def at_row(b, rows_ref, layer_ref):
        return (layer_ref[0], jnp.minimum(rows_ref[b], n_rows - 1), 0, 0, 0)

    o, store = pl.pallas_call(
        functools.partial(_kda_rows_kernel, heads=H, n_rows=n_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, dk, 4 * H), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, 3, H, dv), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, H, dk, dv), at_row),
            ],
            out_specs=[
                pl.BlockSpec((1, H, dv), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, H, dk, dv), at_row),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands: rows, layer, cols, vecs, store -> the store is result 1
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's heads in and out, each double-buffered
            vmem_limit_bytes=4 * H * dk * dv * 4 + (8 << 20)),
        interpret=interpret,
        name="kda_step_rows",
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      cols, vecs, store)
    return o, store


def kda_step_rows(store: jax.Array, layer, rows: jax.Array, q: jax.Array,
                  k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """:func:`kda_step` on rows of a store, written back where they lie.

    store (layers, R, H, dk, dv) float32; ``layer`` which of its layers;
    ``rows`` (B,) int32 the row each batch row steps, DISTINCT, one ``>= R``
    naming none (it reads the store's LAST row and writes it back as it
    is, so no batch row may name that one: it is the engine's staging
    row, which no sequence holds); q, k, g (B, H, dk),
    v (B, H, dv), beta (B, H).  Returns (o (B, H, dv) float32, the store
    with the named rows one token on)."""
    n_rows, H, dk, dv = store.shape[1:]
    if jax.default_backend() == "tpu" and store.dtype == jnp.float32 \
            and dk % 128 == 0 and dv % 128 == 0 \
            and H * dk * dv * 4 <= _ROW_BYTES:
        return _kda_rows_call(store, layer, rows, q, k, v, g, beta)
    state = store[layer][jnp.minimum(rows, n_rows - 1)]
    o, state = kda_step(state, q, k, v, g, beta)
    return o, store.at[layer, rows].set(state, mode="drop")
