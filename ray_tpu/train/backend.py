"""Backend hooks: per-framework worker-group setup.

Reference: ``python/ray/train/backend.py`` + ``train/torch/config.py``
(SURVEY.md §3.4) — the reference's ``TorchConfig`` picks a master address
and calls ``dist.init_process_group("nccl")`` on every worker.  The
TPU-native analog (``JaxConfig``) wires ``jax.distributed``: the driver
allocates a coordinator address through the control plane, every worker
calls ``jax.distributed.initialize(coord, num_processes, process_id)``, and
from then on the worker group is one multi-controller SPMD program domain.

On the CPU test rig (single machine, JAX_PLATFORMS=cpu) multi-process XLA
coordination is unavailable, so ``JaxConfig`` falls back to per-process
local devices + the shm collective group for gradient sync — the same
worker code runs in both worlds.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    """Worker-group lifecycle hooks (reference: ``train.backend.Backend``)."""

    share_cuda_visible_devices = False

    def on_start(self, worker_group, backend_config: "BackendConfig") -> None:
        pass

    def on_training_start(self, worker_group,
                          backend_config: "BackendConfig") -> None:
        pass

    def on_shutdown(self, worker_group, backend_config: "BackendConfig") -> None:
        pass


@dataclass
class JaxConfig(BackendConfig):
    """JAX/TPU worker-group backend.

    use_distributed: multi-controller JAX — every worker process calls
        ``jax.distributed.initialize`` against a coordinator the driver
        allocates, and the group becomes ONE program domain
        (``jax.devices()`` = global device list; one pjit spans all
        workers).  ``True`` forces it anywhere — including the CPU rig,
        where N processes × ``local_device_count`` virtual devices with
        gloo collectives stand in for an N-host slice.  ``None`` (auto)
        enables it on real accelerators with >1 worker when
        ``RTPU_JAX_DISTRIBUTED=1``.
    local_device_count: per-worker virtual device count on the CPU rig
        (ignored on real accelerators — the platform defines locals).
    init_collective_group: also install a shm collective group named
        ``train_default`` across the workers (gradient sync path for the
        non-multi-controller CPU mode; on a real pod the compiled pjit
        program handles it and the shm group is only used for
        control-plane style reductions of metrics).
    """

    use_distributed: Optional[bool] = None   # None = auto (TPU only)
    init_collective_group: bool = True
    coordinator_port: int = 0
    local_device_count: Optional[int] = None
    cpu_collectives: str = "gloo"
    init_timeout_s: float = 120.0
    # preemption-warning subscription (DESIGN.md §4j): called on the
    # DRIVER with each ``node_draining`` fleet event while the run is
    # live — the hook where a training loop arranges an early checkpoint
    # (or hands control to ray_tpu.elastic, which re-meshes instead of
    # restarting).  None = not subscribed.
    drain_handler: Optional[callable] = None
    # --- elastic routing (DESIGN.md §4n) -------------------------------
    # With ``elastic=True``, JaxTrainer.fit() runs through the elastic
    # worker loop (ElasticityManager) instead of the restart-on-failure
    # BackendExecutor: node drains quiesce → re-mesh the surviving
    # jax.distributed domain without a restart, autopilot straggler
    # drains included.  The contract changes with it:
    # ``train_loop_per_worker(config)`` must RETURN a program object
    # with init_state / restore_state / gather_state / step (the
    # ElasticSpec.build contract) — it runs once per mesh generation on
    # every worker, after the generation's domain is up.
    elastic: bool = False
    elastic_total_steps: int = 0          # or train_loop_config["total_steps"]
    elastic_gather_every: int = 1
    elastic_min_workers: int = 1
    elastic_auto_rejoin: bool = True
    elastic_quiesce_timeout_s: float = 60.0
    elastic_timeout_s: float = 600.0

    @property
    def backend_cls(self):
        return _JaxBackend


def _jax_worker_setup(rank: int, world_size: int, coord_addr: Optional[str],
                      group_name: str, init_col: bool,
                      local_devices: Optional[int] = None,
                      cpu_collectives: str = "gloo",
                      init_timeout_s: float = 120.0) -> None:
    if coord_addr is not None and world_size > 1:
        from ray_tpu.parallel import multihost
        multihost.initialize(coord_addr, world_size, rank,
                             local_device_count=local_devices,
                             cpu_collectives=cpu_collectives,
                             init_timeout_s=init_timeout_s)
    if init_col and world_size > 1:
        from ray_tpu.util import collective as col
        if not col.is_group_initialized(group_name):
            col.init_collective_group(world_size, rank, "shm", group_name)


class _JaxBackend(Backend):
    # user-facing alias; the real group name is unique per run+attempt so
    # restarted groups never rendezvous against a dead attempt's KV keys
    GROUP = "train_default"

    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        world = worker_group.num_workers
        use_dist = backend_config.use_distributed
        if use_dist is None:
            # auto: multi-controller init on real accelerators only (the
            # CPU rig opts in explicitly with use_distributed=True)
            use_dist = (not os.environ.get("JAX_PLATFORMS", "")
                        .startswith("cpu") and world > 1
                        and os.environ.get("RTPU_JAX_DISTRIBUTED") == "1")
        import ray_tpu

        # Bounded retry on a lost port race: _free_port() probes by
        # bind-and-close, so under full-suite contention another process
        # can grab the port before the rank-0 coordinator (or a gloo
        # transport) binds it — the rendezvous then dies with
        # EADDRINUSE.  A fresh probe on a fresh attempt is all it takes;
        # anything else (or an explicitly configured port) re-raises
        # immediately.
        for attempt in range(3):
            coord = None
            if use_dist and world > 1:
                import socket
                port = backend_config.coordinator_port or _free_port()
                coord = (f"{socket.gethostbyname(socket.gethostname())}"
                         f":{port}")
            try:
                ray_tpu.get(worker_group.execute_async(
                    _jax_worker_setup_by_rank, world, coord, self.GROUP,
                    backend_config.init_collective_group,
                    backend_config.local_device_count,
                    backend_config.cpu_collectives,
                    backend_config.init_timeout_s))
                return
            except Exception as e:  # noqa: BLE001 - filtered below
                if coord is None or backend_config.coordinator_port \
                        or attempt == 2 or not _is_addr_in_use(e):
                    raise
                # leave whatever half-formed domain exists before the
                # fresh-port attempt (best-effort; ranks that never
                # initialized no-op)
                try:
                    ray_tpu.get(worker_group.execute_async(
                        _jax_worker_teardown), timeout=10)
                except Exception:  # noqa: BLE001 - workers may be dead
                    pass

    def on_training_start(self, worker_group,
                          backend_config: JaxConfig) -> None:
        if backend_config.drain_handler is not None:
            from ray_tpu.elastic.events import FleetEventSubscriber
            self._drain_sub = FleetEventSubscriber(
                backend_config.drain_handler,
                kinds=("node_draining",)).start()

    def on_shutdown(self, worker_group, backend_config: JaxConfig) -> None:
        sub = getattr(self, "_drain_sub", None)
        if sub is not None:
            sub.stop()
            self._drain_sub = None
        # best-effort: leave the jax.distributed domain so coordinator
        # sockets close before the actors are torn down (a force-killed
        # group skips this — the OS reaps)
        import ray_tpu
        try:
            ray_tpu.get(worker_group.execute_async(_jax_worker_teardown),
                        timeout=10)
        except Exception:  # noqa: BLE001 - workers may already be dead
            pass


def _is_addr_in_use(e: BaseException) -> bool:
    """Does this (possibly wrapped) error smell like EADDRINUSE from a
    coordinator / gloo rendezvous bind?"""
    s = str(e).lower()
    return ("eaddrinuse" in s or "address already in use" in s
            or "errno 98" in s)


def _jax_worker_teardown():
    from ray_tpu.parallel import multihost
    multihost.shutdown()


def _jax_worker_setup_by_rank(world, coord, alias, init_col,
                              local_devices=None, cpu_collectives="gloo",
                              init_timeout_s=120.0):
    # Executed via WorkerGroup.execute_async → same fn on every worker; the
    # rank is read from the session (set before backend hooks run).
    from ray_tpu.train._internal.session import get_session
    from ray_tpu.util.collective import collective as col_mod
    s = get_session()
    group = f"train_{s.run_id}_a{s.attempt}"
    _jax_worker_setup(s.rank, world, coord, group, init_col,
                      local_devices, cpu_collectives, init_timeout_s)
    if init_col and world > 1:
        col_mod._register_alias(alias, group)


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]
