"""Pallas fused LayerNorm (TPU) with a fused backward.

Why this exists (r3 device-trace finding; today's kernel time per step is
the ``kernels.layer_norm_ms`` metric, PERF.md §5):
with LayerNorm left to XLA, the compiler chooses a T-minor layout for its
LN fusions (trace: ~32ms/step of LN-backward fusions at the flagship
GPT-2 bench shape, all {1,2,0} layouts).  The Pallas kernel pins the
natural E-minor layout (Pallas operands use default minor-to-major) and
fuses the whole normalize-scale-shift into one VMEM pass each way —
LN-attributed trace time drops to ~4ms/step.  Step-level impact at that
config measured ~neutral (XLA had fused most LN cost into neighboring
ops), so this kernel's value is layout stability + trace legibility +
shapes where XLA's T-minor choice does force stream relayouts.

Semantics match models/gpt2._layer_norm: statistics and affine math in
f32, output cast back to the input dtype.  The backward saves only the
per-row (mu, rstd) f32 stats — O(rows), not O(rows·E) — and emits
per-block partial reductions for dscale/dbias that are summed outside
the kernel (n_blocks × E, trivial).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROWS = 512


def _fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, mu_ref, rstd_ref, *,
                eps: float):
    x = x_ref[...].astype(jnp.float32)                # (R, E)
    mu = x.mean(axis=-1, keepdims=True)
    xc = x - mu
    var = (xc * xc).mean(axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * scale_ref[...].astype(jnp.float32) \
        + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mu_ref[...] = mu[:, 0][None, :]                   # (1, R) lanes
    rstd_ref[...] = rstd[:, 0][None, :]


def _bwd_kernel(x_ref, scale_ref, g_ref, mu_ref, rstd_ref,
                dx_ref, dscale_ref, dbias_ref):
    x = x_ref[...].astype(jnp.float32)                # (R, E)
    g = g_ref[...].astype(jnp.float32)
    mu = jnp.transpose(mu_ref[...])                   # (R, 1)
    rstd = jnp.transpose(rstd_ref[...])
    xhat = (x - mu) * rstd
    gs = g * scale_ref[...].astype(jnp.float32)
    m1 = gs.mean(axis=-1, keepdims=True)
    m2 = (gs * xhat).mean(axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gs - m1 - xhat * m2)).astype(dx_ref.dtype)
    dscale_ref[...] = jnp.sum(g * xhat, axis=0)[None, None, :]  # partial
    dbias_ref[...] = jnp.sum(g, axis=0)[None, None, :]


def _resolve(N: int, interpret: Optional[bool]) -> Tuple[int, bool]:
    rows = DEFAULT_ROWS
    while rows > 8 and N % rows:
        rows //= 2
    if N % rows:
        rows = N  # single block
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return rows, interpret


def _ln_fwd(x2, scale, bias, eps, interpret):
    N, E = x2.shape
    rows, interpret = _resolve(N, interpret)
    nb = N // rows
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((rows, E), lambda i: (i, 0)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((E,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((rows, E), lambda i: (i, 0)),
            pl.BlockSpec((1, rows), lambda i: (0, i)),
            pl.BlockSpec((1, rows), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, E), x2.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_fwd",
    )(x2, scale, bias)
    return y, mu, rstd


def _ln_bwd(x2, scale, g2, mu, rstd, interpret):
    N, E = x2.shape
    rows, interpret = _resolve(N, interpret)
    nb = N // rows
    dx, dscale_p, dbias_p = pl.pallas_call(
        _bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((rows, E), lambda i: (i, 0)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((rows, E), lambda i: (i, 0)),
            pl.BlockSpec((1, rows), lambda i: (0, i)),
            pl.BlockSpec((1, rows), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((rows, E), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, E), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, E), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, E), x2.dtype),
            jax.ShapeDtypeStruct((nb, 1, E), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, E), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_bwd",
    )(x2, scale, g2, mu, rstd)
    return dx, dscale_p.sum(axis=(0, 1)), dbias_p.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5,
               interpret: Optional[bool] = None) -> jax.Array:
    """LayerNorm over the last axis; f32 statistics, affine in f32,
    output in x.dtype.  x: (..., E); scale/bias: (E,)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y, _, _ = _ln_fwd(x2, scale, bias, eps, interpret)
    return y.reshape(shape)


def _vjp_fwd(x, scale, bias, eps, interpret):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y, mu, rstd = _ln_fwd(x2, scale, bias, eps, interpret)
    return y.reshape(shape), (x2, scale, mu, rstd, shape)


def _vjp_bwd(eps, interpret, res, g):
    x2, scale, mu, rstd, shape = res
    g2 = g.reshape(-1, shape[-1])
    dx, dscale, dbias = _ln_bwd(x2, scale, g2, mu, rstd, interpret)
    return (dx.reshape(shape), dscale.astype(scale.dtype),
            dbias.astype(scale.dtype))


layer_norm.defvjp(_vjp_fwd, _vjp_bwd)
