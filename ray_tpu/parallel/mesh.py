"""Device-mesh assembly and sharding rules (the GSPMD heart of the framework).

The reference (Ray) has no notion of a device mesh: its parallelism is
"N actors + NCCL process groups" (reference: ``python/ray/util/collective``,
``python/ray/train/_internal/backend_executor.py``).  The TPU-native design
inverts this (SURVEY.md §7.1): parallelism *inside* a worker group is a single
compiled pjit/shard_map program over a ``jax.sharding.Mesh``, and the
framework's job is assembling that mesh and placing named shardings.

Canonical logical mesh axes (superset of every parallelism the reference's
ecosystem reaches via third-party libs, SURVEY.md §2.4):

======== ============================================ =====================
axis     shards                                       collective traffic
======== ============================================ =====================
data     batch (pure DP)                              grad allreduce
fsdp     batch + parameter shards (ZeRO-3 style)      allgather/reducescatter
pipeline transformer layer blocks (PP stages)         ppermute activations
context  sequence dimension (CP, ring attention)      ppermute KV blocks
seq      sequence dimension BETWEEN blocks (SP:       allgather/reducescatter
         norms/residuals/dropout shard over tokens)   fused into matmul rings
tensor   hidden/heads (Megatron TP)                   allreduce activations
expert   MoE experts (EP)                             all-to-all tokens
======== ============================================ =====================

``seq`` vs ``context``: ``context`` shards the sequence *through*
attention (ring/Ulysses rotate KV so no device ever sees full T);
``seq`` shards the sequence in the regions *between* attention and MLP
(Korthikanti et al. 2022) — layer norms, residual adds and the
optimizer-visible activations live on T/seq tokens per device, and the
boundary all-gather/reduce-scatter legs are folded into the adjacent
projection matmuls by ``ray_tpu.ops.collective_matmul`` so they hide
behind partial-product compute instead of serializing the step.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "fsdp", "pipeline", "context", "seq", "tensor", "expert")


@dataclass(frozen=True)
class MeshConfig:
    """Logical parallelism layout; -1 on ``data`` absorbs remaining devices."""

    data: int = -1
    fsdp: int = 1
    pipeline: int = 1
    context: int = 1
    seq: int = 1
    tensor: int = 1
    expert: int = 1

    def resolved(self, n_devices: int) -> "MeshConfig":
        sizes = self.as_dict()
        fixed = [v for v in sizes.values() if v != -1]
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError("at most one mesh axis may be -1")
        prod = math.prod(fixed)
        if free:
            if n_devices % prod:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[free[0]] = n_devices // prod
        elif prod != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {prod} devices, have {n_devices}")
        return MeshConfig(**sizes)

    def as_dict(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXES}

    @property
    def num_devices(self) -> int:
        return math.prod(v for v in self.as_dict().values())

    def batch_axes(self) -> Tuple[str, ...]:
        """Mesh axes the global batch is sharded over."""
        return tuple(a for a in ("data", "fsdp") if self.as_dict()[a] != 1) \
            or ("data",)


# --------------------------------------------------------------------------
# Ambient mesh: lets model code reach the program mesh at TRACE time (e.g.
# ops/ring_attention wrapping shard_map inside a pjit region).  Set by
# ray_tpu.parallel.spmd around step tracing; plain contextvar — no jax
# global state involved.
# --------------------------------------------------------------------------
import contextlib
import contextvars

_AMBIENT_MESH: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("ray_tpu_ambient_mesh", default=None)


def get_ambient_mesh() -> Optional[Mesh]:
    return _AMBIENT_MESH.get()


@contextlib.contextmanager
def ambient_mesh(mesh: Mesh):
    token = _AMBIENT_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _AMBIENT_MESH.reset(token)


def under_gspmd() -> bool:
    """True where a traced op will be partitioned by GSPMD over several
    devices: the ambient mesh has more than one and no shard_map region
    encloses the trace.  Mosaic (Pallas TPU) kernels ask before they are
    placed — the compiler cannot partition one automatically."""
    mesh = get_ambient_mesh()
    if mesh is None or mesh.size == 1:
        return False
    return not jax.sharding.get_abstract_mesh().manual_axes


def build_mesh(config: MeshConfig,
               devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Assemble a ``jax.sharding.Mesh`` with the canonical axis names.

    Axis order puts ``pipeline``/``data`` outermost (DCN-friendly) and
    ``tensor`` innermost (highest-traffic → shortest ICI hops), matching how
    ``jax.experimental.mesh_utils`` assigns physical adjacency.
    """
    devices = list(devices if devices is not None else jax.devices())
    cfg = config.resolved(len(devices))
    shape = tuple(cfg.as_dict()[a] for a in AXES)
    try:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=np.asarray(devices))
    except Exception:  # noqa: BLE001 - heterogeneous/virtual devices
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def single_device_mesh(device: Optional[Any] = None) -> Mesh:
    dev = device if device is not None else jax.devices()[0]
    return Mesh(np.asarray([dev]).reshape((1,) * len(AXES)), AXES)


# --------------------------------------------------------------------------
# Logical → physical sharding rules (t5x-style, but regex over param paths).
# --------------------------------------------------------------------------

# (param-path regex, PartitionSpec) — first match wins.  Paths are
# "/"-joined pytree keys, e.g. "blocks/attn_qkv/kernel".
Rules = List[Tuple[str, P]]

# Megatron-style 2D(+) sharding for transformer blocks.  ``fsdp`` shards the
# non-tensor dim of every matrix (ZeRO-3); ``tensor`` shards heads/hidden.
# ``blocks/...`` params are STACKED with a leading n_layer axis (lax.scan
# layout, see ray_tpu/models/gpt2.py) — that axis maps to ``pipeline``
# (size 1 unless PP is on, in which case stages own layer ranges).
TRANSFORMER_RULES: Rules = [
    (r".*wte$",                     P("tensor", "fsdp")),   # (vocab, embed)
    (r".*wpe$",                     P(None, "fsdp")),       # (pos, embed)
    (r".*blocks/attn_qkv/kernel$",  P("pipeline", "fsdp", None, "tensor")),
    (r".*blocks/attn_qkv/bias$",    P("pipeline", None, "tensor")),
    (r".*blocks/attn_out/kernel$",  P("pipeline", "tensor", "fsdp")),
    (r".*blocks/attn_out/bias$",    P("pipeline", "fsdp")),
    (r".*blocks/mlp_in/kernel$",    P("pipeline", "fsdp", "tensor")),
    (r".*blocks/mlp_in/bias$",      P("pipeline", "tensor")),
    (r".*blocks/mlp_out/kernel$",   P("pipeline", "tensor", "fsdp")),
    (r".*blocks/mlp_out/bias$",     P("pipeline", "fsdp")),
    (r".*blocks/(ln_1|ln_2)/(scale|bias)$", P("pipeline", None)),
    # stacked experts (layer, expert, in, out): models/llama.py with n_experts
    (r".*blocks/experts/w_(gate|up)$", P("pipeline", "expert", "fsdp", "tensor")),
    (r".*blocks/experts/w_down$",   P("pipeline", "expert", "tensor", "fsdp")),
    # stacked latent-attention blocks (models/deepseek_v3.py: dense_blocks
    # and moe_blocks, whose experts take the two rules above): heads and
    # hidden widths over ``tensor``; the one latent and rotary key of a
    # token, the router and its selection bias are every tensor shard's
    (r".*(dense|moe)_blocks/(wq|wkv_b)/kernel$", P("pipeline", "fsdp", "tensor")),
    (r".*(dense|moe)_blocks/wkv_a/kernel$",     P("pipeline", "fsdp", None)),
    (r".*(dense|moe)_blocks/wo/kernel$",        P("pipeline", "tensor", "fsdp")),
    (r".*(dense|moe)_blocks/(shared/)?w_(gate|up)/kernel$", P("pipeline", "fsdp", "tensor")),
    (r".*(dense|moe)_blocks/(shared/)?w_down/kernel$", P("pipeline", "tensor", "fsdp")),
    (r".*(dense|moe)_blocks/router/(kernel|select_bias)$", P("pipeline")),
    # stacked Qwen3-Next blocks (models/qwen3_next.py: gdn_blocks and
    # attn_blocks, whose experts take the blocks/experts rules above):
    # projections' heads and the conv's channels over ``tensor``, a value
    # head's A_log and dt_bias with it; the router, the shared expert's
    # gate and the norms are every tensor shard's
    (r".*gdn_blocks/in_proj_(qkvz|ba)/kernel$", P("pipeline", "fsdp", "tensor")),
    (r".*gdn_blocks/out_proj/kernel$",          P("pipeline", "tensor", "fsdp")),
    (r".*gdn_blocks/conv/kernel$",              P("pipeline", None, "tensor")),
    (r".*gdn_blocks/(A_log|dt_bias)$",          P("pipeline", "tensor")),
    (r".*attn_blocks/(wq|wk|wv)/kernel$",       P("pipeline", "fsdp", "tensor")),
    (r".*attn_blocks/wo/kernel$",               P("pipeline", "tensor", "fsdp")),
    (r".*(gdn|attn)_blocks/shared/w_(gate|up)/kernel$", P("pipeline", "fsdp", "tensor")),
    (r".*(gdn|attn)_blocks/shared/w_down/kernel$", P("pipeline", "tensor", "fsdp")),
    (r".*(gdn|attn)_blocks/(router|shared_gate)/kernel$", P("pipeline")),
    (r".*(gdn|attn)_blocks/\w+_norm/scale$",    P("pipeline")),
    # stacked Nemotron-H blocks (models/nemotron_h.py: mamba_blocks,
    # expert_blocks and attn_blocks, whose experts take the blocks/experts
    # rules and whose attention the attn_blocks rules above): the mixer's
    # heads and the conv's channels over ``tensor``, a head's A_log, D and
    # dt_bias with it; the router, its bias and the norms are every shard's
    (r".*mamba_blocks/in_proj/kernel$",         P("pipeline", "fsdp", "tensor")),
    (r".*mamba_blocks/out_proj/kernel$",        P("pipeline", "tensor", "fsdp")),
    (r".*mamba_blocks/conv/kernel$",            P("pipeline", None, "tensor")),
    (r".*mamba_blocks/(conv/bias|ssm_norm/scale|A_log|D|dt_bias)$",
     P("pipeline", "tensor")),
    (r".*expert_blocks/shared/w_up/kernel$",    P("pipeline", "fsdp", "tensor")),
    (r".*expert_blocks/shared/w_down/kernel$",  P("pipeline", "tensor", "fsdp")),
    (r".*expert_blocks/router/(kernel|select_bias)$", P("pipeline")),
    (r".*(mamba|expert|attn)_blocks/norm/scale$", P("pipeline")),
    # Non-stacked variants (single-layer modules, BERT/ResNet dense layers).
    (r".*attn_qkv/kernel$",         P("fsdp", None, "tensor")),
    (r".*attn_out/kernel$",         P("tensor", "fsdp")),
    (r".*mlp_in/kernel$",           P("fsdp", "tensor")),
    (r".*mlp_out/kernel$",          P("tensor", "fsdp")),
    (r".*(ln_1|ln_2|ln_f)/(scale|bias)$", P(None)),
    (r".*", P(None)),
]


# Logical ACTIVATION axis → mesh axis.  Params
# are matched by the regex Rules above; intermediate activations are
# placed by logical-axis name through :func:`activation_spec`.  A value
# may be one mesh axis, a tuple of mesh axes (the dim shards over their
# product), or None (replicated).
ACTIVATION_RULES: Dict[str, Any] = {
    "batch": ("data", "fsdp"),     # batch dim: DP (+ ZeRO-3 data shards)
    "seq": ("seq", "tensor"),      # sequence-parallel region BETWEEN
                                   # attention and MLP: tokens shard over
                                   # the dedicated seq axis AND the tensor
                                   # group (Megatron-SP composition) —
                                   # norms/residuals never replicate work
    "seq_attn": "context",         # sequence THROUGH attention (ring CP)
    "heads": "tensor",             # attention heads (Megatron TP)
    "embed": None,                 # residual-stream feature dim
    "mlp": "tensor",               # MLP hidden dim
    "kv": None,                    # per-head feature dim
    "vocab": "tensor",             # logits vocab dim
}


def activation_spec(*logical: Optional[str]) -> P:
    """PartitionSpec for an activation from logical axis names.

    ``activation_spec("batch", "seq", "embed")`` is the canonical
    residual-stream placement between transformer blocks.  ``None``
    entries pass through as replicated dims.
    """
    parts = []
    for name in logical:
        if name is None:
            parts.append(None)
            continue
        if name not in ACTIVATION_RULES:
            raise KeyError(f"unknown logical activation axis {name!r} "
                           f"(have {sorted(ACTIVATION_RULES)})")
        parts.append(ACTIVATION_RULES[name])
    return P(*parts)


def constrain(x, *logical: Optional[str]):
    """Pin an intermediate activation to its logical placement.

    ``constrain(q, "batch", "seq_attn", "heads", "kv")`` applies
    ``with_sharding_constraint`` against the ambient program mesh
    (:func:`get_ambient_mesh`, set by spmd.build_train_program at trace
    time) — inside jit this forces GSPMD to materialize the declared
    layout at that point instead of whatever propagation guessed;
    outside any ambient mesh (unit tests, the serving engine's
    single-host jit) it is a no-op passthrough.  Dims whose mesh-axis
    product does not divide the dim size are left unconstrained (same
    tolerance the param rules get from NamedSharding itself).

    This is the live half of the ``ACTIVATION_RULES`` contract: rtlint's
    meshaxes pass fails on rules no ``constrain()``/``activation_spec()``
    names (``mesh-activation-dead``) and on names no rule declares
    (``mesh-activation-undeclared``).
    """
    mesh = get_ambient_mesh()
    if mesh is None or mesh.empty:
        return x
    sizes = dict(mesh.shape)
    parts = []
    for dim, name in zip(getattr(x, "shape", ()), logical):
        axes = ACTIVATION_RULES.get(name) if name is not None else None
        if name is not None and name not in ACTIVATION_RULES:
            raise KeyError(f"unknown logical activation axis {name!r} "
                           f"(have {sorted(ACTIVATION_RULES)})")
        if axes is None:
            parts.append(None)
            continue
        group = axes if isinstance(axes, tuple) else (axes,)
        group = tuple(a for a in group if a in sizes)
        total = 1
        for a in group:
            total *= sizes[a]
        if not group or total <= 1 or dim % total:
            parts.append(None)
        else:
            parts.append(axes)
    import jax
    if all(p is None for p in parts):
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def spec_for_path(path: str, rules: Rules) -> P:
    for pat, spec in rules:
        if re.fullmatch(pat, path):
            return spec
    return P(None)


def _tree_paths(tree: Any, prefix: str = "") -> Any:
    if isinstance(tree, dict):
        return {k: _tree_paths(v, f"{prefix}/{k}" if prefix else str(k))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        seq = [_tree_paths(v, f"{prefix}/{i}" if prefix else str(i))
               for i, v in enumerate(tree)]
        if hasattr(tree, "_fields"):  # namedtuple: positional constructor
            return type(tree)(*seq)
        return type(tree)(seq)
    return prefix


def param_specs(params: Any, rules: Rules = TRANSFORMER_RULES,
                extra_leading: Optional[str] = None) -> Any:
    """Pytree of PartitionSpecs matching ``params``.

    ``extra_leading`` prepends a mesh axis to every spec (used for stacked
    scan-over-layers params whose leading dim is the layer index → sharded
    over ``pipeline`` when PP is on).
    """
    paths = _tree_paths(params)

    def leaf(path, p):
        spec = spec_for_path(path, rules)
        if extra_leading is not None:
            spec = P(extra_leading, *spec)
        nd = np.ndim(p) if not hasattr(p, "ndim") else p.ndim
        # trim/pad the spec to the leaf's rank
        parts = tuple(spec)[:nd]
        parts = parts + (None,) * (nd - len(parts))
        return P(*parts)

    return jax.tree_util.tree_map(leaf, paths, params)


def _fit_spec(mesh: Mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """``spec`` without the entries whose mesh-axis product does not
    divide the dim they shard (that dim is then replicated)."""
    parts = []
    for dim, axes in zip(shape, tuple(spec)):
        group = axes if isinstance(axes, tuple) else (axes,)
        total = math.prod(mesh.shape[a] for a in group if a is not None)
        parts.append(axes if dim % total == 0 else None)
    return P(*parts)


def named_shardings(mesh: Mesh, specs: Any, like: Any = None) -> Any:
    """NamedShardings for a pytree of PartitionSpecs.  With ``like`` (the
    matching pytree of arrays or shapes) each spec is first fitted to its
    leaf: jit and device_put refuse an uneven sharding, and a rule table
    cannot know that GPT-2's 50,257-row embedding has no even split."""
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    if like is None:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs, is_leaf=is_spec)
    return jax.tree_util.tree_map(
        lambda s, x: NamedSharding(mesh, _fit_spec(mesh, s, x.shape)),
        specs, like, is_leaf=is_spec)


def shard_params(mesh: Mesh, params: Any,
                 rules: Rules = TRANSFORMER_RULES) -> Any:
    """Place a host pytree onto the mesh per the rules (lazy, via device_put)."""
    shardings = named_shardings(mesh, param_specs(params, rules), params)
    return jax.device_put(params, shardings)


def batch_spec(config: MeshConfig, rank: int = 2) -> P:
    """Sharding for a (batch, seq, ...) array: batch over data(+fsdp),
    sequence over context.  The ``seq`` axis deliberately does NOT shard
    the input tokens: (B, T+1) token blocks are rarely divisible by it,
    and the sequence-parallel scatter happens at the manual-region
    boundary inside the step (models/gpt2.py) where T is."""
    axes: List[Any] = [config.batch_axes()]
    if rank >= 2:
        axes.append("context" if config.context != 1 else None)
    axes += [None] * (rank - len(axes))
    return P(*axes)


def local_batch_size(global_batch: int, config: MeshConfig,
                     n_devices: int) -> int:
    cfg = config.resolved(n_devices)
    denom = math.prod(cfg.as_dict()[a] for a in cfg.batch_axes())
    if global_batch % denom:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"data-parallel degree {denom}")
    return global_batch // denom
