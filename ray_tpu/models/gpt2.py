"""GPT-2 family, TPU-first (flagship model for baseline #5, BASELINE.md).

The reference framework (Ray) ships no models — its GPT-2 benchmark runs
torch + DeepSpeed inside Train worker actors (reference:
``python/ray/train/``).  Here the model is a first-class citizen so the
trainer, the mesh layer, and the benchmarks have a common flagship.

Design notes (TPU-first, not a torch translation):
- Pure-JAX pytree params (nested dicts) — transparent to `ray_tpu.parallel.
  mesh` regex sharding rules, `jax.tree_util`, and Orbax checkpointing.
- Per-layer params are STACKED on a leading ``n_layer`` axis and the forward
  pass is a single ``lax.scan`` over blocks: one trace/compile of one block
  regardless of depth (compile-time O(1) in layers), and the leading axis is
  what pipeline parallelism shards.
- ``jax.checkpoint`` (remat) around each block trades FLOPs for HBM.
- bf16 activations / f32 params+optimizer by default: MXU-native.
- Attention is ``ops.attention.causal_attention``: it chooses the Pallas
  flash kernel or XLA's dense attention from the backend and the sequence
  length; ``attn_impl`` passes through to it.  Where the kernel runs on
  heads of 64 (every preset here) the block hands it the fused projection
  whole, heads unsplit (``ops.attention.unsplit_causal_attention``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dtype: Any = jnp.bfloat16          # activation dtype
    # the type init_params stores in: what training keeps and updates
    # (the benchmark's training cells store bf16).  Not what a serving
    # program reads: ModelRunner hands its programs _common.serving_params
    # of the tree, every leaf outside WIDE_PARAMS already in ``dtype``
    param_dtype: Any = jnp.float32
    remat: bool = True
    # _common.remat_block: full recomputes a block in the backward; attn
    # keeps the flash kernel's output and lse, attn_qkv the qkv projection
    # too (both need the flash kernel).  Ignored when remat=False.
    remat_policy: str = "full"  # full | attn | attn_qkv
    # passed to ops.attention.causal_attention, which chooses: "auto" is
    # the Pallas flash kernel on a TPU where its block tiles the sequence
    # and XLA's dense attention elsewhere
    attn_impl: str = "auto"    # auto | dense | flash | ring | ulysses
    # Decomposed collective matmuls (ops/collective_matmul.py): "auto"
    # routes the qkv/attn-out/MLP projections through chunked
    # ppermute-ring all-gather-matmul / matmul-reduce-scatter whenever
    # the ambient mesh has a model axis (seq or tensor > 1) and the
    # shapes divide; "off" keeps GSPMD's serialized collective legs.
    collective_matmul: str = "auto"  # auto | off
    context_axis: Optional[str] = None  # mesh axis for SP/CP ("context")
    pipeline_axis: Optional[str] = None  # mesh axis for PP ("pipeline")
    num_microbatches: int = 0  # 0 = auto (4x stages, divisor of batch)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


# Presets (approx. parameter counts follow the GPT-2 paper sizes).
def gpt2_small() -> GPT2Config:   # 124M
    return GPT2Config(n_embd=768, n_layer=12, n_head=12)


def gpt2_medium() -> GPT2Config:  # 350M
    return GPT2Config(n_embd=1024, n_layer=24, n_head=16)


def gpt2_large() -> GPT2Config:   # 774M
    return GPT2Config(n_embd=1280, n_layer=36, n_head=20)


def gpt2_xl() -> GPT2Config:      # 1.5B — baseline #5 flagship
    return GPT2Config(n_embd=1600, n_layer=48, n_head=25)


def tiny(vocab: int = 256, seq: int = 64) -> GPT2Config:
    """Tiny config for tests and multi-chip dry-runs."""
    return GPT2Config(vocab_size=vocab, n_positions=seq, n_embd=64,
                      n_layer=2, n_head=4)


PRESETS = {"gpt2": gpt2_small, "gpt2-124m": gpt2_small,
           "gpt2-medium": gpt2_medium, "gpt2-large": gpt2_large,
           "gpt2-xl": gpt2_xl, "gpt2-1.5b": gpt2_xl, "tiny": tiny}


# ------------------------------------------------------------------- params
from ray_tpu.models._common import (  # noqa: E402
    next_token_nll, normal_init as _dense_init, param_count, remat_block,
    split_batch)


def init_params(rng: jax.Array, cfg: GPT2Config) -> Params:
    """Initialize params; block leaves stacked on a leading n_layer axis."""
    pd = cfg.param_dtype
    E, H, L = cfg.n_embd, cfg.n_head, cfg.n_layer
    k = iter(jax.random.split(rng, 8 + 4 * L))

    def stack(f):
        return jnp.stack([f(next(k), i) for i in range(L)])

    blocks = {
        "ln_1": {"scale": jnp.ones((L, E), pd), "bias": jnp.zeros((L, E), pd)},
        "attn_qkv": {
            "kernel": stack(lambda kk, i: _dense_init(kk, (E, 3, E), pd)),
            "bias": jnp.zeros((L, 3, E), pd),
        },
        "attn_out": {
            # GPT-2 residual-scaled init: 1/sqrt(2*L)
            "kernel": stack(lambda kk, i: _dense_init(
                kk, (E, E), pd, 0.02 / math.sqrt(2 * L))),
            "bias": jnp.zeros((L, E), pd),
        },
        "ln_2": {"scale": jnp.ones((L, E), pd), "bias": jnp.zeros((L, E), pd)},
        "mlp_in": {
            "kernel": stack(lambda kk, i: _dense_init(kk, (E, 4 * E), pd)),
            "bias": jnp.zeros((L, 4 * E), pd),
        },
        "mlp_out": {
            "kernel": stack(lambda kk, i: _dense_init(
                kk, (4 * E, E), pd, 0.02 / math.sqrt(2 * L))),
            "bias": jnp.zeros((L, E), pd),
        },
    }
    return {
        "wte": _dense_init(next(k), (cfg.vocab_size, E), pd),
        "wpe": _dense_init(next(k), (cfg.n_positions, E), pd, 0.01),
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((E,), pd), "bias": jnp.zeros((E,), pd)},
    }


# The keys whose leaves the forwards use as stored: _layer_norm multiplies
# its scale and adds its bias in float32.  Every other leaf goes through
# _cast at its use, so _common.serving_params may store it in cfg.dtype.
WIDE_PARAMS = ("ln_1", "ln_2", "ln_f")
# The head is tied to the embedding, so ``wte`` has two uses: lm_head
# multiplies by it, _embed fetches rows of it.  The key under which
# _common.serving_params holds it a second time for _embed, its rows
# padded to whole lanes, at a width that is none (XL's 1,600).
ROW_TABLES = {"wte": "wte_rows"}


# ------------------------------------------------------------------ forward
# Every layer kind runs under a ``jax.named_scope`` (embed, ln_1, attn_qkv,
# attn, attn_out, ln_2, mlp, ln_f, lm_head, loss_ce, and cast_weights
# wherever stored weights are cast to the compute dtype).  Metadata only:
# the scope path names the HLO operations, and PERF.md section 3 lists the
# per-layer metric that reads each.
_scope = jax.named_scope


def _cast(w: jax.Array, cfg: "GPT2Config") -> jax.Array:
    """A stored weight in the compute dtype.  A convert only where it is
    stored in another type: training over float32 state, or a forward
    called with a float32 tree.  The serving engine's programs are handed
    ``_common.serving_params`` of the tree (``ModelRunner``), and the
    benchmark's training state is bf16, so there this emits nothing."""
    with _scope("cast_weights"):
        return w.astype(cfg.dtype)


def _embed(params: Params, tokens: jax.Array, positions: jax.Array,
           cfg: "GPT2Config") -> jax.Array:
    with _scope("embed"):
        # a serving tree's second holding of the table where it has one
        # (the pad cut off the fetched rows: the same rows), else ``wte``
        table = params.get(ROW_TABLES["wte"], params["wte"])
        x = _cast(table, cfg)[tokens][..., :cfg.n_embd]
        return x + _cast(params["wpe"], cfg)[positions]


def _layer_norm(x, scale, bias, eps=1e-5):
    """LayerNorm with bias over the last axis, statistics in float32:
    XLA's own, which it fuses into the neighbouring operations at every
    width (and can partition under GSPMD)."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _block(x: jax.Array, lp: Params, cfg: GPT2Config,
           collect_kv: bool = False):
    """One transformer block; with ``collect_kv`` also returns the
    per-head (k, v) — the SAME body serves training and the serving
    engine's prefill cache fill, so the two paths cannot diverge."""
    B, T, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    with _scope("ln_1"):
        h = _layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"])
    from jax.ad_checkpoint import checkpoint_name
    from ray_tpu.ops import attention
    from ray_tpu.parallel import mesh as mesh_lib
    # heads of 64 on the flash kernel: q, k, v stay the projection's three
    # (T, E) planes, which the kernel reads two heads a lane block
    unsplit = attention.unsplit_heads_run(E, H, T, cfg.attn_impl)
    with _scope("attn_qkv"):
        qkv = jnp.einsum("bte,eck->bctk" if unsplit else "bte,eck->btck",
                         h, _cast(lp["attn_qkv"]["kernel"], cfg))
        bias = _cast(lp["attn_qkv"]["bias"], cfg)
        qkv = qkv + (bias[:, None] if unsplit else bias)
        # Named so remat_policy="attn_qkv" can pin it: re-projecting qkv
        # is the one matmul the rematerialized backward would otherwise
        # re-run (the flash kernel's q/k/v residuals flow from here).
        qkv = checkpoint_name(qkv, "attn_qkv")
        if not unsplit:
            q, k, v = [qkv[:, :, i, :].reshape(B, T, H, D) for i in range(3)]
    if unsplit:
        with _scope("attn"):
            a = attention.unsplit_causal_attention(qkv, H)
        if collect_kv:
            k, v = [qkv[:, i].reshape(B, T, H, D) for i in (1, 2)]
    else:
        # Pin the attention-region layout (DESIGN.md §4q /
        # ACTIVATION_RULES): heads shard over tensor, sequence-through-
        # attention over context (ring CP), per-head features replicated.
        # No-op without an ambient mesh; GSPMD otherwise guesses from the
        # qkv matmul.
        q = mesh_lib.constrain(q, "batch", "seq_attn", "heads", "kv")
        k = mesh_lib.constrain(k, "batch", "seq_attn", "heads", "kv")
        v = mesh_lib.constrain(v, "batch", "seq_attn", "heads", "kv")
        with _scope("attn"):
            a = attention.causal_attention(
                q, k, v, impl=cfg.attn_impl,
                context_axis=cfg.context_axis).reshape(B, T, E)
    with _scope("attn_out"):
        a = a @ _cast(lp["attn_out"]["kernel"], cfg) \
            + _cast(lp["attn_out"]["bias"], cfg)
        x = x + a
    with _scope("ln_2"):
        h = _layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"])
    with _scope("mlp"):
        h = h @ _cast(lp["mlp_in"]["kernel"], cfg) \
            + _cast(lp["mlp_in"]["bias"], cfg)
        # MLP hidden shards over tensor (Megatron TP): pinned so the gelu
        # runs on the sharded layout instead of an all-gathered one.
        h = mesh_lib.constrain(h, "batch", "seq_attn", "mlp")
        h = jax.nn.gelu(h, approximate=True)
        h = h @ _cast(lp["mlp_out"]["kernel"], cfg) \
            + _cast(lp["mlp_out"]["bias"], cfg)
        out = x + h
    if collect_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------- overlap-scheduled path
def _manual_parallel_axes(cfg: GPT2Config, mesh, seq_len: int):
    """(sp, tp) when the decomposed/manual region should run, else None.

    The manual region is the overlap-scheduled block: residual stream
    sequence-sharded over (seq × tensor) between attention and MLP
    (Korthikanti et al. 2022 — norms/residual adds never replicate
    work), with the boundary all-gather / reduce-scatter legs folded
    into the projection matmuls as ppermute rings
    (ops/collective_matmul.py) so they hide behind compute.

    A mesh with ``seq > 1`` REQUIRES this path (the axis has no GSPMD
    fallback semantics) — incompatible shapes raise.  ``tensor``-only
    meshes fall back to GSPMD's serialized collectives when the shapes
    don't divide (heads not divisible by tp), preserving the old
    behavior for exotic head counts.
    """
    if cfg.collective_matmul == "off" or mesh is None:
        return None
    from ray_tpu.ops.collective_matmul import model_parallel_sizes
    shape = dict(mesh.shape)
    sp, tp = model_parallel_sizes(mesh)
    if sp * tp == 1:
        return None
    impl = cfg.attn_impl
    ok = (shape.get("context", 1) == 1
          and shape.get("pipeline", 1) == 1
          and impl not in ("ring", "ulysses")
          and cfg.n_head % tp == 0
          and cfg.n_embd % tp == 0 and (4 * cfg.n_embd) % tp == 0
          and seq_len % (sp * tp) == 0)
    if not ok:
        if sp > 1:
            raise ValueError(
                f"mesh has seq={sp} but the sequence-parallel region "
                f"cannot run: needs context=pipeline=1, a "
                f"non-ring/ulysses attn_impl (have {impl!r}), heads/"
                f"embed divisible by tensor={tp}, and seq_len "
                f"({seq_len}) divisible by seq*tensor ({sp * tp})")
        return None
    return sp, tp


def _block_manual(x: jax.Array, lp: Params, *, cfg: GPT2Config,
                  sp: int, tp: int) -> jax.Array:
    """Per-shard transformer block (inside shard_map over the mesh).

    ``x``: (B_local, T_local, E) with T_local = T / (sp·tp) — the
    sequence-parallel residual stream.  Layer norms and residual adds
    run on local tokens only; the four projections are decomposed
    collective matmuls over the ``tensor`` ring (all-gather-matmul in,
    matmul-reduce-scatter out) so their collective legs overlap their
    own partial products; attention runs on the T/sp sequence chunk —
    the Pallas flash kernel (or dense) at full T when sp == 1, the
    ppermute KV ring over the ``seq`` axis when sp > 1 (ring attention
    IS flash attention's online-softmax update walked around the ring,
    so the seq axis composes with the flash block layout instead of
    fighting it)."""
    from ray_tpu.ops import collective_matmul as cm
    B, Tl, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    Hl = H // tp

    with _scope("ln_1"):
        h = _layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"])
    with _scope("attn_qkv"):
        wqkv = _cast(lp["attn_qkv"]["kernel"], cfg).reshape(E, 3 * Hl * D)
        qkv = cm.all_gather_matmul(h, wqkv, "tensor", tp)  # (B, T/sp, 3E/tp)
        qkv = qkv + _cast(lp["attn_qkv"]["bias"], cfg).reshape(-1)
        Ts = Tl * tp                                       # = T / sp
        qkv = qkv.reshape(B, Ts, 3, Hl, D)
        from jax.ad_checkpoint import checkpoint_name
        qkv = checkpoint_name(qkv, "attn_qkv")
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    with _scope("attn"):
        if sp > 1:
            from ray_tpu.ops.ring_attention import ring_attention
            a = ring_attention(q, k, v, axis_name="seq", axis_size=sp,
                               causal=True)
        else:
            from ray_tpu.ops.attention import causal_attention
            a = causal_attention(q, k, v, impl=cfg.attn_impl)
    with _scope("attn_out"):
        wout = _cast(lp["attn_out"]["kernel"], cfg).reshape(Hl * D, E)
        aout = cm.matmul_reduce_scatter(a.reshape(B, Ts, Hl * D), wout,
                                        "tensor", tp)      # (B, Tl, E)
        # biases ride AFTER the reduce-scatter: inside it they would be
        # summed tp times
        x = x + aout + _cast(lp["attn_out"]["bias"], cfg)

    with _scope("ln_2"):
        h = _layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"])
    with _scope("mlp"):
        m = cm.all_gather_matmul(
            h, _cast(lp["mlp_in"]["kernel"], cfg), "tensor", tp)
        m = jax.nn.gelu(m + _cast(lp["mlp_in"]["bias"], cfg),
                        approximate=True)
        mo = cm.matmul_reduce_scatter(
            m, _cast(lp["mlp_out"]["kernel"], cfg), "tensor", tp)
        return x + mo + _cast(lp["mlp_out"]["bias"], cfg)


def _manual_block_specs(cfg: GPT2Config):
    """shard_map in_specs for one layer's params in the manual region.

    Only ``tensor`` appears: fsdp-sharded dims are declared replicated,
    so GSPMD inserts the ZeRO-3 all-gather at the region boundary (and
    its transpose reduce-scatters the grads) — weight resharding stays
    GSPMD's job, activation collectives are ours."""
    from jax.sharding import PartitionSpec as P
    del cfg
    ln = {"scale": P(None), "bias": P(None)}
    return {
        "ln_1": dict(ln),
        "attn_qkv": {"kernel": P(None, None, "tensor"),
                     "bias": P(None, "tensor")},
        "attn_out": {"kernel": P("tensor", None), "bias": P(None)},
        "ln_2": dict(ln),
        "mlp_in": {"kernel": P(None, "tensor"), "bias": P("tensor")},
        "mlp_out": {"kernel": P("tensor", None), "bias": P(None)},
    }


def forward_hidden(params: Params, tokens: jax.Array,
                   cfg: GPT2Config) -> jax.Array:
    """tokens (B, T) int32 → final-LN hidden states (B, T, E) in cfg.dtype."""
    B, T = tokens.shape
    # Arrays here are GLOBAL (GSPMD view) even when the sequence dim is
    # sharded over the context axis — only the attention impl drops into
    # shard_map (where chunk offsets come from lax.axis_index).
    x = _embed(params, tokens, jnp.arange(T), cfg)

    from ray_tpu.parallel import mesh as mesh_lib
    amb_mesh = mesh_lib.get_ambient_mesh()
    manual = _manual_parallel_axes(cfg, amb_mesh, T)
    if manual is not None:
        # Overlap-scheduled region: shard_map over the whole mesh, the
        # residual stream sequence-sharded over (seq × tensor), every
        # projection a decomposed collective matmul.  x enters/leaves
        # per-shard as (B_local, T/(sp·tp), E).
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        sp, tp = manual
        xspec = P(("data", "fsdp"), ("seq", "tensor"), None)
        block = shard_map(
            partial(_block_manual, cfg=cfg, sp=sp, tp=tp),
            mesh=amb_mesh, in_specs=(xspec, _manual_block_specs(cfg)),
            out_specs=xspec, check_vma=False)
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(amb_mesh,
                             mesh_lib.activation_spec("batch", "seq",
                                                      "embed")))
    else:
        block = partial(_block, cfg=cfg)
    if cfg.remat:
        from ray_tpu.ops.attention import flash_runs
        # the seq > 1 KV ring of the manual region is not the flash kernel
        sp = 1 if manual is None else manual[0]
        block = remat_block(block, cfg.remat_policy,
                            flash_runs(T, cfg.attn_impl) and sp == 1)

    def scan_body(carry, lp):
        return block(carry, lp), None

    pp_mesh = None
    if cfg.pipeline_axis is not None:
        from ray_tpu.parallel import mesh as mesh_lib
        pp_mesh = mesh_lib.get_ambient_mesh()
        if pp_mesh is None:
            # Loud, not silent: tracing with PP configured but no ambient
            # mesh would bake a non-pipelined program into the jit cache.
            raise RuntimeError(
                "cfg.pipeline_axis is set but no ambient mesh is installed; "
                "trace inside ray_tpu.parallel.mesh.ambient_mesh(mesh) "
                "(spmd.build_train_program does this)")
    if pp_mesh is not None and pp_mesh.shape[cfg.pipeline_axis] > 1:
        # Pipeline-parallel block stack: stages ride ppermute over the
        # pipeline mesh axis; within a stage, the usual scan over its layer
        # slice.  Remat stays per-block (scan_body), not per-stage.
        from ray_tpu.parallel import pipeline as pp_lib
        S = pp_mesh.shape[cfg.pipeline_axis]
        staged = pp_lib.stack_stages(params["blocks"], S)
        M = cfg.num_microbatches or pp_lib.pick_num_microbatches(B, S)

        def stage_fn(sp, xm):
            y, _ = lax.scan(scan_body, xm, sp)
            return y

        x = pp_lib.merge_microbatches(pp_lib.pipeline_apply(
            stage_fn, staged, pp_lib.split_microbatches(x, M),
            mesh=pp_mesh, axis=cfg.pipeline_axis, remat=False))
    else:
        x, _ = lax.scan(scan_body, x, params["blocks"])
    with _scope("ln_f"):
        x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return x


def forward(params: Params, tokens: jax.Array,
            cfg: GPT2Config) -> jax.Array:
    """tokens (B, T) int32 → logits (B, T, vocab) in f32."""
    x = forward_hidden(params, tokens, cfg)
    with _scope("lm_head"):
        logits = jnp.einsum("bte,ve->btv", x, _cast(params["wte"], cfg))
        # Vocab dim shards over tensor (the wte is tensor-sharded on
        # vocab): pinned so the (B, T, V) f32 logits never replicate.
        from ray_tpu.parallel import mesh as mesh_lib
        logits = mesh_lib.constrain(logits, "batch", None, "vocab")
        return logits.astype(jnp.float32)


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: GPT2Config) -> jax.Array:
    """Next-token cross entropy. batch: {"tokens": (B, T+1) int32} or
    {"inputs","targets"} pair of (B, T)."""
    inp, tgt = split_batch(batch)
    x = forward_hidden(params, inp, cfg)
    with _scope("lm_head"):
        logits = jnp.einsum("bte,ve->btv", x, _cast(params["wte"], cfg))
    return next_token_nll(logits, tgt)


# -------------------------------------------------- inference (KV cache)
def forward_prefill(params: Params, tokens: jax.Array, cfg: GPT2Config,
                    last_pos: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill forward: tokens (B, T) → (logits, k, v) with
    k/v (L, B, T, H, D) — the per-layer KV the serving engine scatters
    into its paged pool (serve/llm, DESIGN.md §4g).

    ``last_pos`` (traced scalar): compute logits ONLY at that sequence
    position, returned as (B, V) — prompts are bucket-padded, so the
    full (B, T, V) head projection would be mostly wasted work and
    device→host traffic.  None returns the full (B, T, V)."""
    B, T = tokens.shape
    x = _embed(params, tokens, jnp.arange(T), cfg)

    def body(carry, lp):
        return _block(carry, lp, cfg, collect_kv=True)

    x, (ks, vs) = lax.scan(body, x, params["blocks"])
    with _scope("ln_f"):
        x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    with _scope("lm_head"):
        if last_pos is not None:
            x = lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        logits = jnp.einsum("bte,ve->btv", x, _cast(params["wte"], cfg))
        if last_pos is not None:
            logits = logits[:, 0]
        return logits.astype(jnp.float32), ks, vs


def forward_decode(params: Params, tokens: jax.Array, positions: jax.Array,
                   kv_pool: jax.Array, block_tables: jax.Array,
                   ctx_lens: jax.Array,
                   cfg: GPT2Config) -> Tuple[jax.Array, jax.Array,
                                             jax.Array]:
    """One decode step over the paged KV pool.

    tokens/positions (B,) int32; kv_pool — the engine's block pool, on
    the device, whole, in the format ``ops/paged_attention`` reads
    (read-only here: the new token's K/V is returned, the runner's
    program writes it); block_tables (B, MAXB) int32;
    ctx_lens (B,) int32.  Returns (logits (B, V) f32,
    new_k (L, B, H, D), new_v (L, B, H, D)).
    """
    from ray_tpu.ops.paged_attention import paged_attention_decode
    B = tokens.shape[0]
    E, H, D = cfg.n_embd, cfg.n_head, cfg.head_dim
    x = _embed(params, tokens, positions, cfg)                  # (B, E)

    def body(carry, xs):
        x = carry
        lp, layer = xs
        with _scope("ln_1"):
            h = _layer_norm(x[:, None, :], lp["ln_1"]["scale"],
                            lp["ln_1"]["bias"])[:, 0]
        with _scope("attn_qkv"):
            qkv = jnp.einsum("be,eck->bck",
                             h, _cast(lp["attn_qkv"]["kernel"], cfg))
            qkv = qkv + _cast(lp["attn_qkv"]["bias"], cfg)
            q, k, v = [qkv[:, i, :].reshape(B, H, D) for i in range(3)]
        with _scope("attn"):
            # the pool whole and this layer's index: nothing of it is
            # sliced or copied on the way to the kernel
            a = paged_attention_decode(q, kv_pool, layer, block_tables,
                                       ctx_lens, k, v).reshape(B, E)
        with _scope("attn_out"):
            a = a @ _cast(lp["attn_out"]["kernel"], cfg) \
                + _cast(lp["attn_out"]["bias"], cfg)
            x = x + a
        with _scope("ln_2"):
            h = _layer_norm(x[:, None, :], lp["ln_2"]["scale"],
                            lp["ln_2"]["bias"])[:, 0]
        with _scope("mlp"):
            h = h @ _cast(lp["mlp_in"]["kernel"], cfg) \
                + _cast(lp["mlp_in"]["bias"], cfg)
            h = jax.nn.gelu(h, approximate=True)
            h = h @ _cast(lp["mlp_out"]["kernel"], cfg) \
                + _cast(lp["mlp_out"]["bias"], cfg)
            return x + h, (k, v)

    x, (ks, vs) = lax.scan(body, x,
                           (params["blocks"], jnp.arange(cfg.n_layer)))
    with _scope("ln_f"):
        x = _layer_norm(x[:, None, :], params["ln_f"]["scale"],
                        params["ln_f"]["bias"])[:, 0]
    with _scope("lm_head"):
        logits = jnp.einsum("be,ve->bv", x, _cast(params["wte"], cfg))
        return logits.astype(jnp.float32), ks, vs


def flops_per_token(cfg: GPT2Config, seq_len: int) -> float:
    """Approximate train-step FLOPs/token (fwd+bwd ≈ 6*N + attention term)."""
    n = param_count_analytic(cfg)
    attn = 12 * cfg.n_layer * cfg.n_embd * seq_len  # 2*2*3 * L * E * T
    return 6 * n + attn


def param_count_analytic(cfg: GPT2Config) -> int:
    E, L, V, Pn = cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_positions
    per_layer = 12 * E * E + 13 * E
    return V * E + Pn * E + L * per_layer + 2 * E
