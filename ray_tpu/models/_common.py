"""What the decoders share: init and count helpers, the rule that makes a
serving tree of a stored one, the remat rule of a block, the batch's two
forms, the loss head, and the primitives more than one family's block is
written with (RMS norm, the three rotary embeddings, the GQA repeat).  Each
model file keeps its own block, parameter tree and named scopes, and
imports no sibling's private name: what two families need lives here."""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def normal_init(key: jax.Array, shape, dtype, scale: float = 0.02):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def param_count(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def tree_bytes(tree: Any) -> int:
    """Bytes of a tree's leaves (arrays or ``ShapeDtypeStruct``s)."""
    return sum(int(x.size) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _stays_wide(path, wide: Tuple[str, ...]) -> bool:
    return any(getattr(k, "key", None) in wide for k in path)


@partial(jax.jit, static_argnames=("dtype", "wide"))
def _cast_leaves(params: Any, dtype: Any, wide: Tuple[str, ...]) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if _stays_wide(path, wide) else w.astype(dtype),
        params)


# The lanes of a tile of the device's memory: a table whose rows are a
# whole number of them is held in row order, any other in column order.
LANES = 128


@jax.jit
def _pad_rows(table: jax.Array) -> jax.Array:
    return jnp.pad(table, ((0, 0), (0, -table.shape[-1] % LANES)))


def serving_params(params: Any, dtype: Any, wide: Tuple[str, ...],
                   row_tables: Optional[Dict[str, str]] = None) -> Any:
    """A stored parameter tree as the inference forwards should be handed
    it: the same structure and names, each weight stored once in the type
    the forward computes in, and a table that is both multiplied and
    gathered from a second time, in the order the gather reads.

    A forward casts a stored weight to ``cfg.dtype`` at its use
    (``gpt2._cast``, ``.astype(cfg.dtype)`` in llama.py,
    ``w.astype(x.dtype)`` in ops/moe.py).  On a float32 tree under a bf16
    config that convert is part of every program run: XLA hoists it out of
    the scan over layers as one pass over the whole stack, 13.85 of a
    27 ms GPT-2 XL decode step (PERF.md, PR 31).  Handed this tree, the
    same ``astype`` emits no operation.  A convert is exact and each
    matmul took ``dtype`` operands already, so the arithmetic is the same.

    ``wide``: the keys under which the family's forward uses a leaf as it
    is stored (norm scales and biases, multiplied in float32); the model
    module states them beside its ``init_params`` (``WIDE_PARAMS``).
    Every other leaf is cast.

    ``row_tables``: top-level (rows, width) leaf -> the key of its second
    holding (``ROW_TABLES`` of a module whose head is tied to its
    embedding, so that one leaf has two uses).  The device holds a
    parameter by its SHAPE, not by its uses: rows of whole lanes in row
    order, any other width in column order, the compact one (row-major
    tiles would pad every row: GPT-2 XL's 1,600 is 12.5 x 128).  The
    head's matmul reads column order in place; a gather of rows cannot,
    so every XL step copied all 161 MB of ``wte`` to row order to fetch
    8 rows, 0.50 of its 5.88 ms (PERF.md, PR 41; ``compiled.as_text()``:
    the parameter ``{0,1}``, a ``copy`` of it to ``{1,0}``).  So at such
    a width the tree gains the table once more, zeros behind each row up
    to whole lanes: that leaf is held in row order, each of the two has
    one use, and both are read in place.  The forward cuts the zeros off
    the rows it fetched (``gpt2._embed``): the same rows, the same
    values.  At a width of whole lanes one leaf serves both uses and
    nothing is added.

    A jitted call for each; where no leaf would change type and no table
    is added it returns ``params`` itself and builds no program."""
    dtype, wide = jnp.dtype(dtype), tuple(wide)
    if not all(_stays_wide(path, wide) or w.dtype == dtype
               for path, w in jax.tree_util.tree_leaves_with_path(params)):
        params = _cast_leaves(params, dtype=dtype, wide=wide)
    rows = {held: _pad_rows(params[name])
            for name, held in (row_tables or {}).items()
            if params[name].shape[-1] % LANES and held not in params}
    return {**params, **rows} if rows else params


def _keep_scopes_under_checkpoint() -> None:
    """Lower ``jax.checkpoint``'s equation in place, not through jax's
    cache of lowered equations.

    A cached equation is lowered once into a function whose operations
    all carry the call site's location, so everything inside a
    checkpointed block's backward (its recomputation and its transpose:
    most of a train step) would reach the compiled module without its
    ``jax.named_scope`` path, and ``tracing.op_map`` could not say what a
    backward operation is.  Lowered in place the operations keep their
    own locations.  Locations only: the StableHLO is the same text, and
    so is the compile-cache key (tests/test_op_map.py pins both).  jax
    keeps this switch for "primitives that have problems with caching"
    (``register_lowering(cacheable=False)``); where a later jax has no
    such set, the scopes are lost again and nothing else."""
    try:
        from jax._src import ad_checkpoint
        from jax._src.interpreters import mlir
        mlir._uncacheable_primitives.add(ad_checkpoint.remat_p)
    except (ImportError, AttributeError):
        pass


def remat_block(block: Callable, policy: str, flash_runs: bool) -> Callable:
    """``block`` under ``jax.checkpoint`` by a config's ``remat_policy``.

    full: recompute the whole block in the backward (least memory).
    attn: keep only the flash kernel's output and its compact lse (tagged
    with ``checkpoint_name`` in ops/flash_attention.py), so the backward
    does not run the forward kernel again: the largest recompute of a
    step, and what lets GPT-2 XL fit one chip.  attn_qkv: also keep the
    projection a block tags ``attn_qkv``, the one matmul the replay would
    re-run ((B, T, 3E) a layer: right for small models, too much from
    GPT-2 medium up at b32/s1024 on a 16 GB chip).

    ``flash_runs``: whether this program's attention is the flash kernel
    (``ops.attention.flash_runs``, and no sequence-axis KV ring).  The
    kept names exist only inside that kernel's vjp; without it ``attn*``
    would silently be full remat, so it raises."""
    _keep_scopes_under_checkpoint()
    if policy == "full":
        return jax.checkpoint(block)
    if policy not in ("attn", "attn_qkv"):
        raise ValueError(f"unknown remat_policy {policy!r} "
                         "(expected full | attn | attn_qkv)")
    if not flash_runs:
        raise ValueError(
            f"remat_policy={policy!r} keeps what only the flash kernel "
            "names, and flash attention will not run here: it needs "
            "attn_impl 'flash', or 'auto' on a TPU, a sequence length the "
            "kernel's block tiles, and no seq-axis KV ring")
    names = ["flash_attn_out", "flash_attn_lse"]
    if policy == "attn_qkv":
        names.append("attn_qkv")
    return jax.checkpoint(
        block, policy=jax.checkpoint_policies.save_only_these_names(*names))


def experts_in_place(experts: Dict[str, jax.Array]) -> Tuple[jax.Array, ...]:
    """A stack's expert leaves ``w_gate`` (where the family's expert has
    one: ``ops/moe.dropless_experts``), ``w_up``, ``w_down``, each (layers,
    H, ...), as a training scan's body takes them beside its own slice:
    whole, ``(layers x H, ...)`` (a bitcast), gradient stopped.

    The grouped matmuls are kernels, and a kernel's operand is a buffer: a
    layer's experts sliced out of their stack by the scan were copied whole
    once in the forward loop and once in the backward's (805 MB a layer a
    loop at OLMoE's widths, 14 of a 250 ms step: PERF.md, PR 66).  So the
    body closes over these and hands them on with the layer's index
    (``ops/moe.dropless_experts``' ``stack``): the kernels read the layer's
    groups where they lie, the scan's own slice is read by nothing, is
    dropped from both loops, and is where the layer's gradient goes, in the
    layer's shape.  A constant of both loops, not a residual a layer."""
    return tuple(lax.stop_gradient(experts[name]).reshape(
        -1, *experts[name].shape[2:]) for name in ("w_gate", "w_up", "w_down")
        if name in experts)


def split_batch(batch: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """A training batch's (inputs, targets), each (B, T): the pair as
    given, or ``{"tokens": (B, T+1)}`` shifted by one."""
    if "inputs" in batch:
        return batch["inputs"], batch["targets"]
    return batch["tokens"][:, :-1], batch["tokens"][:, 1:]


def next_token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token negative log-likelihood of (B, T, V) logits in the
    activation dtype.

    logsumexp, NOT log_softmax: log_softmax materializes a second
    (B, T, V) float32 tensor just to read one element a row.  The
    target's logit is read from the activation-dtype logits, so the
    float32 convert has exactly one consumer (the lse reduce) and XLA
    fuses it without materializing float32 logits at all (trace-measured
    ~14 ms a step on a v5e at b32/s1024, r3)."""
    with jax.named_scope("loss_ce"):
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        correct = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return (lse - correct.astype(jnp.float32)).mean()


# ------------------------------------- primitives of more than one family
def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings over (B, T, H, D); rotates pairs (d, d+D/2)."""
    B, T, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]  # (1, T, 1, half)
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _rope_at(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding for single tokens at explicit positions.

    x (B, H, D); positions (B,) int32 — the absolute position of each
    sequence's token (decode caches post-RoPE keys, so each key is
    rotated once, at its own position)."""
    B, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[:, None, :]            # (B, 1, half)
    sin = jnp.sin(angles)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over (B, T, H, D) on the pairs (2i, 2i + 1):
    position t turns pair i by t . theta^(-2i / D)."""
    B, T, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(B, T, H, half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(B, T, H, D).astype(x.dtype)


def _gqa_expand(kv: jax.Array, n_head: int) -> jax.Array:
    """(B, T, n_kv, D) → (B, T, n_head, D) by repeating KV groups."""
    B, T, n_kv, D = kv.shape
    if n_kv == n_head:
        return kv
    rep = n_head // n_kv
    return jnp.repeat(kv, rep, axis=2)
