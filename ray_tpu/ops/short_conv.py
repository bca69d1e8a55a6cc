"""The gated short convolution (LFM2's conv mixer), between its two
projections: for one token's projection ``[B | C | x]`` (three segments of
E channels, in that order)::

    z_t = B_t * x_t
    c_t = sum_j w[j] * z_{t-(K-1)+j}      depthwise, causal, zeros before
                                          the sequence, no bias, no
                                          activation (w[K-1]: the current z)
    g_t = C_t * c_t

in float32.  The conv is ``ops/ssm.py``'s (``causal_conv`` for a sequence,
``conv_step`` for a token); a sequence's state is its *tail*, the last
K-1 ``z``, which the sequence form returns at ``last_pos``: a prompt's
padding is poison for it as for any recurrence, and the tail is cut at the
last real position.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm


def _gates(p: jax.Array):
    """(..., 3 E) -> B, C, x (..., E), float32."""
    b, c, x = jnp.split(p.astype(jnp.float32), 3, axis=-1)
    return b, c, x


def gated_conv(p: jax.Array, w: jax.Array,
               last_pos: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """p (B, T, 3 E), w (K, E) -> (g (B, T, E) float32, the tail at
    ``last_pos`` (B, K-1, E) float32, or None without it)."""
    b, c, x = _gates(p)
    y, tail = ssm.causal_conv(b * x, w, None, last_pos)
    return c * y, tail


def gated_conv_step(tail: jax.Array, p: jax.Array, w: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """One token: tail (R, K-1, E) float32, p (R, 3 E) -> (g (R, E)
    float32, the tail one token on)."""
    b, c, x = _gates(p)
    y, tail = ssm.conv_step(tail, b * x, w, None)
    return c * y, tail
