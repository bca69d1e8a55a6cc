"""TPU-first optimizers: HBM-compact AdamW (low-precision moments).

Reference contrast: the reference framework's train integrations wrap torch
optimizers inside worker actors (reference: ``python/ray/train/torch/``),
with f32 state resident per replica and DDP syncing grads at runtime.  On a
16GB-HBM TPU chip the optimizer state IS the capacity wall: f32 Adam moments
for GPT-2-1.5B are 12.5GB alone, and the optimizer phase of the train step
is HBM-bandwidth-floored (15.1ms of f32 state traffic at the flagship bench
config in the r3 device trace).  Storing moments in bf16
halves both the footprint and the traffic; the update MATH stays f32 — the
storage dtype only bounds what survives between steps.

Numerics: bf16 has f32's exponent range and ~3 significant digits.  EMA
increments are a fixed fraction of the running value ((1-b1)=10%,
(1-b2)=2-5% per step), far above bf16's ~0.4% ulp, so the moment EMAs track.
This is the same regime as widely-deployed 8-bit Adam — and strictly more
conservative.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import optax


def scale_by_adam_compact(
        b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
        mu_dtype: Any = jnp.bfloat16,
        nu_dtype: Any = jnp.bfloat16) -> optax.GradientTransformation:
    """``optax.scale_by_adam`` with BOTH moments stored in a compact dtype.

    optax's own ``mu_dtype`` covers only the first moment; the second moment
    (same size) stays f32 there.  Update math is f32 throughout: moments are
    upcast, blended with the f32-cast gradient, used for the update, and
    only the carried state is downcast.
    """
    mu_dtype = jnp.dtype(mu_dtype)
    nu_dtype = jnp.dtype(nu_dtype)

    def init(params):
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=mu_dtype), params),
            nu=jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=nu_dtype), params))

    def update(updates, state, params=None):
        del params
        count = state.count + 1
        c = count.astype(jnp.float32)
        bc1 = 1.0 - jnp.power(jnp.float32(b1), c)
        bc2 = 1.0 - jnp.power(jnp.float32(b2), c)

        def blend(g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * g32 * g32
            u = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
            # the update leaves f32 — downstream transforms (weight decay,
            # lr scale) and the apply-add run in f32; only carried state
            # is compact
            return u, m32.astype(mu_dtype), v32.astype(nu_dtype)

        out = jax.tree_util.tree_map(blend, updates, state.mu, state.nu)
        new_updates, new_mu, new_nu = jax.tree_util.tree_transpose(
            jax.tree_util.tree_structure(updates),
            jax.tree_util.tree_structure((0, 0, 0)), out)
        return new_updates, optax.ScaleByAdamState(
            count=count, mu=new_mu, nu=new_nu)

    return optax.GradientTransformation(init, update)


# Leaves a loss reads and never differentiates: a router's selection bias
# (models/deepseek_v3.py: a choice has no gradient).  Their gradient is
# zero, so Adam's update of them is zero; weight decay must leave them too.
BUFFER_KEYS = ("select_bias",)


def decayed(params: Any) -> Any:
    """The weight-decay mask: every leaf but those under ``BUFFER_KEYS``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not any(getattr(k, "key", None) in BUFFER_KEYS
                                for k in path), params)


def adamw_compact(
        learning_rate: Union[float, Callable[[jax.Array], jax.Array]],
        *, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
        weight_decay: float = 0.0, clip: Optional[float] = None,
        mu_dtype: Any = jnp.bfloat16,
        nu_dtype: Any = jnp.bfloat16) -> optax.GradientTransformation:
    """AdamW with compact moment storage (drop-in for ``optax.adamw``)."""
    parts = []
    if clip is not None:
        parts.append(optax.clip_by_global_norm(clip))
    parts += [
        scale_by_adam_compact(b1=b1, b2=b2, eps=eps,
                              mu_dtype=mu_dtype, nu_dtype=nu_dtype),
        optax.add_decayed_weights(weight_decay, mask=decayed),
        optax.scale_by_learning_rate(learning_rate),
    ]
    return optax.chain(*parts)


def apply_updates_mixed(params: Any, updates: Any) -> Any:
    """``optax.apply_updates`` with the ADD in f32.

    With bf16 master params (the only way GPT-2-XL + moments fit 16GB on one
    chip) ``p + u`` in bf16 loses any update below ~0.4% of the weight —
    i.e. almost all of them.  Upcasting for the add keeps the common
    magnitude-cancellation error one rounding, matching how TPU mixed-
    precision recipes apply weight updates.  For f32 params this is
    bit-identical to ``optax.apply_updates``.
    """
    def add(p, u):
        if u is None:
            return p
        return (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype)

    return jax.tree_util.tree_map(add, params, updates,
                                  is_leaf=lambda x: x is None)
