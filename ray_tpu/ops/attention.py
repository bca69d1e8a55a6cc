"""Causal attention for training and prefill: the choice, and its pieces.

The reference framework (Ray) contains no kernels at all (SURVEY.md §5.7);
these are greenfield TPU-first components.

- ``causal_attention``: what every decoder block calls.  It chooses the
  implementation from what it can see (the backend, the sequence length,
  the ambient mesh), as ``ops/paged_attention.paged_attention_decode``
  does for decode; ``flash_runs`` is the one statement of when the Pallas
  kernel (``ops/flash_attention.py``) runs.
- ``unsplit_causal_attention``: for a caller that holds q, k and v as ONE
  fused projection with heads of 64 unsplit (GPT-2): the flash kernels
  read two heads a 128-lane block in place; ``unsplit_heads_run`` says
  when, from the widths, ``flash_runs`` and the ambient mesh.
- ``latent_causal_attention``: the same choice for a caller whose
  queries and keys come in two column groups with ONE rotary key for all
  heads (latent attention): on the kernel the five operands go as they
  are, off it they are joined and ``causal_attention`` goes on.
- ``dense_attention``: XLA's O(T²) attention, the path everywhere the
  kernel does not run and the tests' reference.
- ``flash_update`` / ``flash_finalize``: the online-softmax block update
  that ring attention (``ops/ring_attention.py``) walks around its ring.

Accumulators are float32 regardless of input dtype (bf16-safe softmax).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = jnp.finfo(jnp.float32).min


def flash_update(o: jax.Array, m: jax.Array, l: jax.Array,
                 q: jax.Array, k: jax.Array, v: jax.Array,
                 mask: Optional[jax.Array],
                 scale: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax accumulation step.

    Shapes: q (B,Tq,H,D); k,v (B,Tk,H,D); o (B,H,Tq,D) f32;
    m,l (B,H,Tq) f32; mask broadcastable to (B,H,Tq,Tk) bool (True=keep).

    Rows with no valid key yet keep ``m == NEG_INF``; callers must ensure
    the FIRST block every row sees has at least one valid key (causal ring
    starts with the diagonal block) so ``m`` is finite before fully-masked
    blocks contribute exp(NEG_INF - m) == 0.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o = o * corr[..., None] + pv
    return o, m_new, l


def flash_finalize(o: jax.Array, l: jax.Array, dtype) -> jax.Array:
    """(B,H,T,D) f32 accumulators → (B,T,H,D) normalized output."""
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(dtype)


def causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """(Tq,), (Tk,) global positions → (Tq, Tk) bool keep-mask."""
    return q_pos[:, None] >= k_pos[None, :]


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True,
                    q_offset: int | jax.Array = 0,
                    block: int = 1) -> jax.Array:
    """Plain O(T²) attention (B,T,H,D); the XLA-fused short-sequence path.
    ``v`` may be (B,T,H,Dv) with Dv != D: the scale is 1/sqrt(D), the
    keys' width, and the output is Dv wide.

    ``q_offset`` shifts query positions for causal masking when q is a
    chunk of a longer sequence (used by decode / chunked prefill).
    ``block`` > 1: the mask is block-causal, a position sees every earlier
    block of that many positions whole and its own in both directions
    (``k_pos // block <= q_pos // block``); 1 is the causal mask.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = jnp.arange(k.shape[1])
        if block > 1:
            q_pos, k_pos = q_pos // block, k_pos // block
        mask = causal_mask(q_pos, k_pos)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


IMPLS = ("auto", "dense", "flash", "ring", "ulysses")


def flash_runs(seq_len: int, impl: str = "auto") -> bool:
    """Whether ``causal_attention`` runs the Pallas flash kernel on a
    sequence of this length: asked for (``"flash"``; off a TPU that is
    the kernel's interpret mode) or seen (``"auto"`` on a TPU backend),
    and the kernel's best block tiles the sequence.  A length with no
    clean tile (a 192-token serving bucket: block 128 does not divide)
    takes XLA's dense attention and must not take the engine down."""
    if impl not in IMPLS:
        raise ValueError(f"unknown attn_impl {impl!r} (expected one of "
                         f"{' | '.join(IMPLS)})")
    wanted = impl == "flash" or (impl == "auto"
                                 and jax.default_backend() == "tpu")
    if not wanted:
        return False
    from ray_tpu.ops.flash_attention import pick_block_size
    return seq_len % pick_block_size(seq_len) == 0


def unsplit_heads_run(n_embd: int, n_head: int, seq_len: int,
                      impl: str = "auto") -> bool:
    """Whether ``unsplit_causal_attention`` runs for a caller that holds
    q, k and v as ONE fused projection (B, 3, T, E) with its heads unsplit:
    the flash kernel runs (``flash_runs``), the heads are 64 wide, so two
    fill a 128-lane block of E, and no ambient mesh splits the heads or the
    sequence through attention (GSPMD would have to cut inside E).  A head
    of 128 has no second head in its lanes, and a caller with grouped or
    shared keys holds three arrays: both call ``causal_attention``."""
    from ray_tpu.ops.flash_attention import HEAD
    if n_embd != n_head * HEAD or not flash_runs(seq_len, impl):
        return False
    from ray_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.get_ambient_mesh()
    if mesh is None or mesh.empty:
        return True
    for axes in mesh_lib.activation_spec("seq_attn", "heads"):
        for axis in (axes,) if isinstance(axes, str) else axes or ():
            if mesh.shape.get(axis, 1) > 1:
                return False
    return True


def unsplit_causal_attention(qkv: jax.Array, n_head: int) -> jax.Array:
    """Causal self-attention on a fused projection (B, 3, T, E), the planes
    q, k, v with ``n_head`` heads of 64 side by side in E, -> (B, T, E),
    where ``unsplit_heads_run`` says so: the flash kernels read a PAIR of
    heads a 128-lane block in place and write the output and the
    projection's gradient the same way, so nothing is re-laid between the
    projection, the kernel and the output projection."""
    from ray_tpu.ops.flash_attention import flash_attention_pairs
    return flash_attention_pairs(qkv, n_head)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     impl: str = "auto",
                     context_axis: Optional[str] = None,
                     block: int = 1) -> jax.Array:
    """Causal self-attention over (B, T, H, D) for training and prefill;
    values may be narrower than keys, which the flash kernel and the dense
    path take as they are, with no padding.  (A caller with a rotary key
    shared by its heads has ``latent_causal_attention``.)

    ``impl`` is a model config's ``attn_impl``: ``auto`` (the flash
    kernel where ``flash_runs`` says so, XLA's dense attention
    elsewhere), ``dense``, ``flash``, or one of the context-parallel
    schedules ``ring`` / ``ulysses`` over the ambient mesh's
    ``context_axis`` (dense where the mesh does not split that axis).

    ``block`` > 1: the block-causal mask of a model that generates by
    diffusion over blocks (``dense_attention``); the flash kernel masks its
    diagonal tile by blocks, and the context-parallel schedules have no
    such mask."""
    if flash_runs(q.shape[1], impl):
        from ray_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, block if block > 1 else True)
    if block > 1:
        if impl in ("ring", "ulysses"):
            raise NotImplementedError(
                f"attn_impl {impl!r} has no block-causal mask")
        return dense_attention(q, k, v, causal=True, block=block)
    if impl in ("ring", "ulysses"):
        from ray_tpu.parallel import mesh as mesh_lib
        axis = context_axis or "context"
        mesh = mesh_lib.get_ambient_mesh()
        if mesh is not None and mesh.shape.get(axis, 1) > 1:
            if impl == "ring":
                from ray_tpu.ops.ring_attention import (
                    ring_attention_sharded as sharded)
            else:
                from ray_tpu.ops.ulysses import (
                    ulysses_attention_sharded as sharded)
            return sharded(q, k, v, mesh=mesh, axis_name=axis, causal=True)
    return dense_attention(q, k, v, causal=True)


def latent_causal_attention(q_nope: jax.Array, q_rope: jax.Array,
                            k_nope: jax.Array, k_rope: jax.Array,
                            v: jax.Array, *, impl: str = "auto") -> jax.Array:
    """Causal self-attention for a caller that holds its queries and keys
    in two column groups and ONE rotary key for all heads (latent
    attention): q_nope, k_nope (B,T,H,nope), q_rope (B,T,H,rope), k_rope
    (B,T,rope), v (B,T,H,Dv) -> (B,T,H,Dv); the score is
    ``q_nope·k_nopeᵀ + q_rope·k_ropeᵀ`` over 1/sqrt(nope + rope).

    Where ``flash_runs`` says so the flash kernels take the five operands
    as they are (``flash_attention.latent_flash_attention``).  Everywhere
    else the parts are joined, the rotary key repeated a head, and
    ``causal_attention`` goes on as for any other caller."""
    if flash_runs(v.shape[1], impl):
        from ray_tpu.ops.flash_attention import latent_flash_attention
        return latent_flash_attention(q_nope, q_rope, k_nope, k_rope, v)
    k_rope = jnp.broadcast_to(k_rope[:, :, None],
                              k_nope.shape[:3] + k_rope.shape[-1:])
    return causal_attention(jnp.concatenate([q_nope, q_rope], -1),
                            jnp.concatenate([k_nope, k_rope], -1), v,
                            impl=impl)
