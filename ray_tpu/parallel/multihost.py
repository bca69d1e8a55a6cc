"""Multi-controller (multi-process) SPMD runtime.

Reference: ``python/ray/train/torch/config.py`` (SURVEY.md §3.4) — the
reference's worker-group backend calls ``dist.init_process_group("nccl")``
on every worker so the group becomes one communicator domain.  The
TPU-native analog is **multi-controller JAX**: every worker process calls
``jax.distributed.initialize(coordinator, num_processes, process_id)``,
after which ``jax.devices()`` is the GLOBAL device list and one pjit
program spans all processes — XLA inserts the cross-host collectives
(ICI/DCN on a real pod; gloo on the CPU rig).

This module is the thin, framework-owned wrapper the Train backend and
the dryrun harness share:

- ``initialize()`` — config-safe setup.  On the CPU rig it pins the
  per-process virtual device count (``jax_num_cpu_devices`` wins over any
  inherited ``--xla_force_host_platform_device_count`` flag) and selects
  the gloo cross-process collective implementation; on a real TPU pod
  both knobs are no-ops and the call reduces to the stock
  ``jax.distributed.initialize``.
- ``gather_to_host()`` / ``put_global()`` — checkpoint plumbing: a
  cross-process-sharded pytree is gathered to plain numpy on EVERY
  process (so any rank can write a full checkpoint), and restored by
  re-placing host arrays against global shardings (``jax.device_put``
  has global semantics when every process holds the same host value).

The CPU rig (N processes × ``jax_num_cpu_devices`` each, gloo) stands in
for an N-host TPU slice exactly the way the reference's gloo CI rig
stands in for NCCL.
"""

from __future__ import annotations

from typing import Any, Optional

__all__ = [
    "initialize", "shutdown", "is_distributed", "process_index",
    "process_count", "gather_to_host", "put_global",
]


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, *, local_device_count: Optional[int] = None,
               cpu_collectives: str = "gloo",
               init_timeout_s: Optional[float] = None) -> None:
    """Join this process to a multi-controller JAX program domain.

    Must run before the process's first device query (the backend is
    initialized lazily on first use; config updates after that raise).

    local_device_count: per-process device count on the CPU platform
        (virtual-host rig).  Ignored on real accelerators, where the
        platform defines the local devices.
    cpu_collectives: cross-process collective implementation for the CPU
        platform ("gloo" or "mpi"); ignored elsewhere.
    """
    import os

    import jax

    if num_processes <= 1:
        return
    # Effective platform: the env var when set, else the jax_platforms
    # config.  Empty means "auto" — on a CPU-only host that resolves to
    # cpu, so apply the CPU knobs then too: both are no-ops for a process
    # whose default backend turns out to be a real accelerator
    # (jax_num_cpu_devices only shapes the cpu platform's device list and
    # cpu_collectives only affects cpu cross-process transfers).
    platform = (os.environ.get("JAX_PLATFORMS")
                or getattr(jax.config, "jax_platforms", None)
                or "").split(",")[0]
    if platform in ("cpu", ""):
        if local_device_count:
            jax.config.update("jax_num_cpu_devices", int(local_device_count))
        if cpu_collectives:
            jax.config.update("jax_cpu_collectives_implementation",
                              cpu_collectives)
    kw: dict = dict(coordinator_address=coordinator_address,
                    num_processes=num_processes, process_id=process_id)
    if init_timeout_s is not None:
        kw["initialization_timeout"] = int(init_timeout_s)
    jax.distributed.initialize(**kw)


def shutdown() -> None:
    """Leave the program domain (idempotent, best-effort)."""
    try:
        import jax
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 - never initialized / already down
        pass


def is_distributed() -> bool:
    import jax
    return jax.process_count() > 1


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def gather_to_host(tree: Any) -> Any:
    """Sharded pytree → numpy pytree of GLOBAL values on every process.

    The multi-controller checkpoint path: ``jax.device_get`` alone
    cannot read non-addressable shards, so each leaf rides a
    ``process_allgather`` (an XLA all-gather across the processes) and
    lands as a full host array everywhere — any rank can then persist a
    complete checkpoint, and a restarted group of a DIFFERENT size can
    still restore it.  Single-process trees pass through via device_get.
    """
    import jax
    import numpy as np

    if not is_distributed():
        return jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), tree)
    from jax.experimental import multihost_utils

    return jax.tree_util.tree_map(
        lambda x: np.asarray(multihost_utils.process_allgather(x, tiled=True)),
        tree)


def put_global(tree: Any, shardings: Any) -> Any:
    """Host (numpy) pytree → globally-sharded device arrays.

    Every process must hold the SAME host values (the ``gather_to_host``
    contract); ``jax.device_put`` then transfers only each process's
    addressable shards.
    """
    import jax

    return jax.tree_util.tree_map(
        lambda h, sh: jax.device_put(h, sh), tree, shardings)
