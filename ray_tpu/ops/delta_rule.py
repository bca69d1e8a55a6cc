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
two products ``T`` is applied in) multiplies float32 operands at
``HIGHEST``: an error in ``T`` is an error in every correction of the
chunk.  The other products (k . k, q . k, and the four against the
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
from jax.ad_checkpoint import checkpoint_name

_HI = lax.Precision.HIGHEST
CHUNK = 64
SPAN = 16       # chunks whose (C, C) work is made at once, and kept at once
INVERSE = "delta_rule_inverse"      # the name the solve's result is kept by


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


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
    v's type (the module's head)."""
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
    if state0 is None:
        state0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    state, o = lax.scan(
        jax.checkpoint(functools.partial(_span, dtype=dtype),
                       policy=jax.checkpoint_policies.save_only_these_names(
                           INVERSE)),
        state0.astype(jnp.float32).reshape(B, G, R, dk, dv), xs)
    # (S, B, G, R, m, C, dv) -> (B, T, H, dv)
    o = o.transpose(1, 0, 4, 5, 2, 3, 6).reshape(B, S * m * C, H, dv)[:, :T]
    return o, state.reshape(B, H, dk, dv)
