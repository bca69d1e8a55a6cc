"""SPMD train-program assembly: mesh + sharding rules + optax → one jit.

Reference contrast: Ray Train assembles torch DDP process groups around the
user's loop (reference: ``python/ray/train/_internal/backend_executor.py``,
``train/torch/config.py``); gradients sync via NCCL calls at runtime.  Here
the whole training step — forward, backward, gradient "allreduce", optimizer
— is ONE compiled XLA program over the mesh; data/tensor/context parallel
collectives are inserted by GSPMD and ride ICI (SURVEY.md §5.8 item 3).
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.mesh import MeshConfig, Rules, TRANSFORMER_RULES
from ray_tpu.util import tracing


@dataclass
class TrainState:
    """Minimal train state pytree (flax-free so sharding rules stay simple)."""
    step: jax.Array
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.step, s.params, s.opt_state), None),
    lambda _, c: TrainState(*c))


# What a loss function computes beside its loss and wants in the step's
# metrics (routing statistics, auxiliary terms): None outside a step's
# trace, the step's dictionary inside it.
_STEP_METRICS: contextvars.ContextVar[Optional[Dict[str, jax.Array]]] = \
    contextvars.ContextVar("ray_tpu_step_metrics", default=None)


def report_step_metrics(**scalars: jax.Array) -> None:
    """Called by a loss function at its top level (not inside a scan or a
    checkpointed block: the values leave the trace they are made in as
    auxiliary outputs of the loss): the step built by
    :func:`build_train_program` returns them under these names beside
    ``loss``.  Anywhere else a no-op, so the loss stays a scalar function."""
    sink = _STEP_METRICS.get()
    if sink is not None:
        sink.update(scalars)


@contextlib.contextmanager
def _collect_step_metrics():
    sink: Dict[str, jax.Array] = {}
    token = _STEP_METRICS.set(sink)
    try:
        yield sink
    finally:
        _STEP_METRICS.reset(token)


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.01,
                      warmup: int = 100, total_steps: int = 10_000,
                      b2: float = 0.95, clip: float = 1.0,
                      moments_dtype: Any = None) -> optax.GradientTransformation:
    """AdamW with warmup-cosine schedule.  ``moments_dtype`` (e.g.
    ``jnp.bfloat16``) stores BOTH Adam moments compactly — halves the
    optimizer's HBM footprint and its bandwidth-floored step phase
    (parallel/optim.py); None keeps optax's f32 state.  Weight decay
    leaves out what a loss never differentiates (``optim.BUFFER_KEYS``)."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), end_value=lr * 0.1)
    from ray_tpu.parallel.optim import adamw_compact, decayed
    if moments_dtype is not None:
        return adamw_compact(sched, b1=0.9, b2=b2,
                             weight_decay=weight_decay, clip=clip,
                             mu_dtype=moments_dtype, nu_dtype=moments_dtype)
    return optax.chain(optax.clip_by_global_norm(clip),
                       optax.adamw(sched, b1=0.9, b2=b2,
                                   weight_decay=weight_decay, mask=decayed))


def state_specs(state: TrainState, rules: Rules) -> TrainState:
    """PartitionSpecs for a TrainState: params by rules; opt-state moments
    mirror their param's spec; scalars replicated."""
    pspecs = mesh_lib.param_specs(state.params, rules)

    def opt_leaf_spec(leaf):
        # Adam moments have the same shape as params; match by shape lookup.
        shape = getattr(leaf, "shape", ())
        spec = shape_index.get(tuple(shape))
        return spec if spec is not None else P()

    shape_index: Dict[tuple, P] = {}
    flat_p = jax.tree_util.tree_leaves_with_path(state.params)
    flat_s = jax.tree_util.tree_leaves(pspecs)
    for (path, leaf), spec in zip(flat_p, flat_s):
        shape_index.setdefault(tuple(leaf.shape), spec)

    ospecs = jax.tree_util.tree_map(opt_leaf_spec, state.opt_state)
    return TrainState(step=P(), params=pspecs, opt_state=ospecs)


@dataclass
class SpmdProgram:
    """A compiled distributed training step and its placement metadata.

    ``init_fn`` and ``step_fn`` are plain functions, what a trainer calls
    (each carries its set-up span and the step its compile budget).
    Whatever needs the ``jax.jit`` object goes through ``jitted_init`` /
    ``jitted_step``: ``.lower()`` / ``.compile()``, and
    ``jax.eval_shape(program.jitted_init, key)``, which gives the state's
    shapes WITH their shardings (off a plain function it gives none)."""
    mesh: Mesh
    mesh_config: MeshConfig
    init_fn: Callable[[jax.Array], TrainState]     # sharded init
    step_fn: Callable[[TrainState, Any], Tuple[TrainState, Dict[str, jax.Array]]]
    state_shardings: Any
    batch_sharding: Any
    # the jax.jit object behind step_fn, for .lower()/.compile() (what
    # the step was compiled to: kernels, collectives, memory)
    jitted_step: Any = None
    # the jax.jit object behind init_fn: ``jax.eval_shape(jitted_init,
    # key)`` is the state as shapes WITH its shardings
    jitted_init: Any = None
    # (state, batch) as shapes, kept at the step's first call
    abstract_args: Optional[tuple] = None
    # set-up span totals, name -> [count, seconds] (tracing.setup_span):
    # ``train.init`` a call of init_fn, ``train.compile`` the step's first
    span_s: Dict[str, list] = field(default_factory=dict)

    def op_map(self) -> Dict[str, dict]:
        """What each instruction of the compiled step is
        (``tracing.op_map``): lowers with the first call's shapes and
        compiles, both hits in jax's in-memory caches in the process that
        ran the step, then parses the module's text."""
        if self.abstract_args is None:
            raise RuntimeError("the step has not been called yet: its "
                               "batch's shape is not known")
        return tracing.op_map(
            self.jitted_step.lower(*self.abstract_args).compile())


def build_train_program(
        *, loss_fn: Callable[[Any, Any], jax.Array],
        init_params_fn: Callable[[jax.Array], Any],
        optimizer: Optional[optax.GradientTransformation] = None,
        mesh_config: Optional[MeshConfig] = None,
        mesh: Optional[Mesh] = None,
        rules: Rules = TRANSFORMER_RULES,
        batch_rank: int = 2,
        donate_state: bool = True,
        donate_batch: bool = False,
        accum_steps: int = 1,
        accum_dtype: Any = None) -> SpmdProgram:
    """Assemble the one-jit distributed train step.

    ``loss_fn(params, batch) -> scalar``; GSPMD derives every collective from
    the shardings — there is no explicit allreduce anywhere.  Scalars the
    loss function hands to :func:`report_step_metrics` join the step's
    metrics (means over microbatches under ``accum_steps``); a loss that
    reports nothing compiles to the program it always did.

    ``accum_steps > 1`` runs microbatch gradient accumulation INSIDE the one
    jit: the global batch is split on its leading dim into ``accum_steps``
    microbatches and a ``lax.scan`` accumulates grads before one optimizer
    update.  Activation memory scales with the MICRObatch, so batch sizes
    that OOM outright fit (the r3 sweep's HBM-OOM rows; VERDICT r3 #1).
    ``accum_dtype`` sets the accumulator dtype (default: the grad dtype —
    pass ``jnp.bfloat16`` to halve accumulator HBM when params are f32).
    """
    tracing.listen_to_compiles()
    optimizer = optimizer or default_optimizer()
    if mesh is None:
        mesh_config = (mesh_config or MeshConfig()).resolved(
            len(jax.devices()))
        mesh = mesh_lib.build_mesh(mesh_config)
    else:
        mesh_config = (mesh_config or MeshConfig()).resolved(mesh.size)

    # Shapes-only init to derive shardings without materializing params.
    abstract_params = jax.eval_shape(init_params_fn, jax.random.key(0))
    abstract_state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=abstract_params,
        opt_state=jax.eval_shape(optimizer.init, abstract_params))
    specs = state_specs(
        TrainState(step=None, params=abstract_params,
                   opt_state=abstract_state.opt_state), rules)
    state_sh = TrainState(
        step=NamedSharding(mesh, P()),
        params=mesh_lib.named_shardings(mesh, specs.params,
                                        abstract_params),
        opt_state=mesh_lib.named_shardings(mesh, specs.opt_state,
                                           abstract_state.opt_state))
    batch_sh = NamedSharding(mesh, mesh_lib.batch_spec(mesh_config, batch_rank))

    def _init(rng: jax.Array) -> TrainState:
        params = init_params_fn(rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    jitted_init = jax.jit(_init, out_shardings=state_sh)

    def init_fn(rng: jax.Array) -> TrainState:
        # once in a program's life, so every call carries the span.  A
        # plain function: ``jax.eval_shape`` reads the state's shardings
        # off ``program.jitted_init``, not off this
        with tracing.setup_span("train.init", program.span_s, "train.init"):
            return jitted_init(rng)

    def _loss_and_reported(params: Any, batch: Any):
        with _collect_step_metrics() as reported:
            loss = loss_fn(params, batch)
        return loss, reported

    def _grads(params: Any, batch: Any):
        """-> ((loss, what the loss function reported), grads)."""
        # Runs at trace time: model code (e.g. ring attention) can pick up
        # the program mesh via mesh_lib.get_ambient_mesh() to nest shard_map.
        with mesh_lib.ambient_mesh(mesh), jax.named_scope("grads"):
            return jax.value_and_grad(_loss_and_reported, has_aux=True)(
                params, batch)

    def _grads_accum(params: Any, batch: Any):
        # Microbatch split on the leading (batch) dim.  The reshape keeps
        # the data-parallel sharding on the microbatch dim (constraint
        # below) so each scan iteration is the same SPMD program at 1/A
        # batch; the accumulator is carried state, the activations die with
        # each iteration.
        A = accum_steps

        def split(x):
            if getattr(x, "ndim", 0) == 0 or x.shape[0] % A:
                raise ValueError(
                    f"batch dim {getattr(x, 'shape', ())} not divisible "
                    f"by accum_steps={A}")
            mb = x.reshape(A, x.shape[0] // A, *x.shape[1:])
            spec = mesh_lib.batch_spec(mesh_config, mb.ndim - 1)
            return jax.lax.with_sharding_constraint(
                mb, NamedSharding(mesh, P(None, *spec)))

        mbs = jax.tree_util.tree_map(split, batch)
        acc0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, accum_dtype or p.dtype), params)

        def body(carry, mb):
            loss_acc, g_acc = carry
            (loss, reported), grads = _grads(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), g_acc, grads)
            return (loss_acc + loss, g_acc), reported

        with jax.named_scope("grad_accum"):
            (loss_sum, acc), reported = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), acc0), mbs)
            inv = jnp.float32(1.0 / A)
            grads = jax.tree_util.tree_map(
                lambda a, p: (a.astype(jnp.float32) * inv).astype(p.dtype),
                acc, params)
            reported = {k: v.mean(0) for k, v in reported.items()}
            return (loss_sum * inv, reported), grads

    def _step(state: TrainState, batch: Any):
        if accum_steps > 1:
            (loss, reported), grads = _grads_accum(state.params, batch)
        else:
            (loss, reported), grads = _grads(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            from ray_tpu.parallel.optim import apply_updates_mixed
            params = apply_updates_mixed(state.params, updates)
        new = TrainState(step=state.step + 1, params=params,
                         opt_state=opt_state)
        gnorm = optax.global_norm(grads)
        return new, {**reported, "loss": loss, "grad_norm": gnorm,
                     "step": new.step.astype(jnp.float32)}

    # Donation: the WHOLE TrainState — params AND both Adam moments —
    # aliases its output buffers (in/out shardings match leaf-for-leaf,
    # so XLA reuses every buffer in place; the optimizer phase is
    # HBM-bandwidth-floored and an un-donated moment tree would double
    # its traffic AND its footprint).  ``donate_batch`` additionally
    # donates the input batch for callers that feed a fresh batch every
    # step (streaming ingest, train_bench) — never for callers that
    # re-feed one batch (bench.py's steady-state loop).
    donate: Tuple[int, ...] = (0,) if donate_state else ()
    if donate_batch:
        donate = donate + (1,)
    step_fn = jax.jit(
        _step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, NamedSharding(mesh, P())),
        donate_argnums=donate)

    # XLA watchdog step region (DESIGN.md §4q): one program for this
    # SpmdProgram's life (COMPILE_BUDGETS["train.step"]), zero host
    # transfers inside the dispatch.  Callers' device_get of the
    # metrics dict happens on THEIR side of the region and stays legal.
    from ray_tpu._private.xla_watchdog import compile_budget
    step_budget = compile_budget("train.step")

    def first_step(state: TrainState, batch: Any):
        # keep the shapes the step is called with, so that
        # tracing.op_maps() can say what its operations are.  A reference
        # and nothing else: nothing is lowered or parsed until someone asks
        program.abstract_args = tracing.abstract((state, batch))
        tracing.register_program("train.step", step_fn,
                                 program.abstract_args)
        # what building the step costs, to the enqueue of its first run
        with tracing.setup_span("train.compile", program.span_s,
                                "train.step"), step_budget:
            return step_fn(state, batch)

    def guarded_step(state: TrainState, batch: Any):
        if program.abstract_args is None:
            return first_step(state, batch)
        with step_budget:
            return step_fn(state, batch)

    program = SpmdProgram(
        mesh=mesh, mesh_config=mesh_config, init_fn=init_fn,
        step_fn=guarded_step, state_shardings=state_sh,
        batch_sharding=batch_sh, jitted_step=step_fn,
        jitted_init=jitted_init)
    return program


def shard_batch(program: SpmdProgram, batch: Any) -> Any:
    """Host batch (numpy pytree) → device arrays with the batch sharding."""
    def put(x):
        rank = getattr(x, "ndim", 0)
        sh = NamedSharding(program.mesh,
                           mesh_lib.batch_spec(program.mesh_config, rank))
        return jax.device_put(x, sh)
    return jax.tree_util.tree_map(put, batch)
