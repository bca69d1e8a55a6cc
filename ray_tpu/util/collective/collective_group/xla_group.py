"""In-mesh collective backend: compiled XLA collectives over ICI.

Reference: ``python/ray/util/collective/collective_group/nccl_collective_group.py``
— but per SURVEY.md §5.8 the TPU-native inversion is that intra-slice
collectives are *compiled into the program*, not runtime library calls.
This group therefore lives inside ONE process that owns N local devices
(a TPU host owns its chips under single-controller JAX); each op is a
jitted ``shard_map`` collective over a 1-D mesh of those devices, executed
over ICI.  This is the path the ``allreduce bus bandwidth`` baseline
(BASELINE.md #6) measures.

Data layout convention: ops accept either
- an array whose leading axis is the device axis (shape ``(n_dev, ...)``),
  sharded or not — it is sharded over the mesh on entry; or
- a list of ``n_dev`` per-device arrays (stacked for you).
Results come back with the same leading device axis.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.util.collective.types import ReduceOp

AXIS = "col"

_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


class XlaCollectiveGroup:
    """A device-set collective group with compiled ops (cached per shape)."""

    def __init__(self, devices: Optional[Sequence[Any]] = None,
                 group_name: str = "default"):
        devs = list(devices if devices is not None else jax.devices())
        self.group_name = group_name
        self.mesh = Mesh(np.asarray(devs), (AXIS,))
        self.world_size = len(devs)

    # ------------------------------------------------------------- helpers
    def _stack(self, tensor: Any) -> jax.Array:
        if isinstance(tensor, (list, tuple)):
            tensor = jnp.stack([jnp.asarray(t) for t in tensor])
        tensor = jnp.asarray(tensor)
        if tensor.shape[0] != self.world_size:
            raise ValueError(
                f"leading axis {tensor.shape[0]} != group size {self.world_size}")
        sharding = NamedSharding(self.mesh, P(AXIS))
        return jax.device_put(tensor, sharding)

    @functools.lru_cache(maxsize=64)
    def _compiled(self, kind: str, op: ReduceOp, shape: tuple, dtype: Any):
        mesh = self.mesh
        spec = P(AXIS)

        # Per-device block always has leading axis 1 (global leading axis is
        # the device axis, sharded over the mesh); bodies return leading
        # axis 1 so out_specs=P(AXIS) reassembles the device axis.
        if kind == "allreduce":
            def body(x):
                return _REDUCERS[op](x, AXIS)
        elif kind == "allgather":
            def body(x):  # x: (1, ...) → (1, world, ...)
                return jax.lax.all_gather(x[0], AXIS, tiled=False)[None]
        elif kind == "reducescatter":
            def body(x):  # x: (1, world, ...) → (1, ...)
                return jax.lax.psum_scatter(x[0], AXIS, scatter_dimension=0,
                                            tiled=False)[None]
        elif kind == "alltoall":
            def body(x):  # x: (1, world, ...) → (1, world, ...) transposed
                return jax.lax.all_to_all(x[0], AXIS, split_axis=0,
                                          concat_axis=0, tiled=False)[None]
        else:
            raise ValueError(kind)

        from jax import shard_map
        fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec)
        return jax.jit(fn)

    # ----------------------------------------------------------------- ops
    def allreduce(self, tensor: Any, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
        """All-reduce over the device axis; result replicated per device
        (leading axis preserved: out[i] == reduce(in[:, ...]) for all i)."""
        op = ReduceOp.coerce(op)
        if op == ReduceOp.PRODUCT:
            raise NotImplementedError(
                "PRODUCT allreduce is not compiled; use SUM/MIN/MAX "
                "(reference NCCL supports prod; add on demand)")
        x = self._stack(tensor)
        fn = self._compiled("allreduce", op, x.shape, x.dtype)
        return fn(x)

    def allgather(self, tensor: Any) -> jax.Array:
        """Per-device rows gathered: out shape (world, world, ...)."""
        x = self._stack(tensor)
        fn = self._compiled("allgather", ReduceOp.SUM, x.shape, x.dtype)
        return fn(x)

    def reducescatter(self, tensor: Any, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
        """In: (world, world, ...) — row i is device i's contribution list.
        Out: (world, ...) — device i holds sum_j in[j, i]."""
        x = self._stack(tensor)
        fn = self._compiled("reducescatter", ReduceOp.coerce(op), x.shape,
                            x.dtype)
        return fn(x)

    def alltoall(self, tensor: Any) -> jax.Array:
        """In: (world, world, ...); out[i, j] = in[j, i] (transpose over
        devices — the EP/Ulysses dispatch primitive)."""
        x = self._stack(tensor)
        fn = self._compiled("alltoall", ReduceOp.SUM, x.shape, x.dtype)
        return fn(x)

    def barrier(self) -> None:
        # A collective that must complete on all devices.
        jax.block_until_ready(
            self.allreduce(jnp.zeros((self.world_size, 1), jnp.int32)))

    def destroy(self) -> None:
        self._compiled.cache_clear()


# `functools.lru_cache` on a method holds self; acceptable here (groups are
# long-lived and destroy() clears), but make hashing identity-based:
XlaCollectiveGroup.__hash__ = object.__hash__
XlaCollectiveGroup.__eq__ = object.__eq__
