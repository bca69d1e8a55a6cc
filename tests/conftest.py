"""Test rig (SURVEY.md §4 testing blueprint).

- CPU JAX with 8 virtual devices stands in for a TPU slice so all
  collective / pjit / shard_map paths run in CI without hardware
  (reference pattern: gloo CPU tests standing in for NCCL).
- ``ray_start_regular`` starts a fresh single-node cluster per test;
  ``ray_start_cluster`` yields a multi-node ``Cluster`` fixture.
"""

import os

# Must run before jax is imported anywhere in the test process: the tests
# run on the CPU with 8 virtual devices and never open a chip (only
# chip_smoke.py does).  Spawned workers inherit this environment.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("RTPU_OBJECT_STORE_MEMORY_MB", "256")

# The compile cache of this process and of every worker it spawns: a
# directory of its own, empty at the start and gone at the end, whatever
# the environment or ``xla_cache_dir`` (which defaults into the checkout)
# say.  A run must not read what an earlier run left: on a filled
# ``.xla_cache/`` tests/test_spmd.py::test_odd_vocab_trains_on_a_tensor_mesh
# aborted its xdist worker inside XLA's CPU runtime, loading an executable
# it would otherwise have compiled.  Set before jax or ray_tpu is imported,
# so that every process of the run has it from its first compile on.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="rtpu_test_xla_cache_")
atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                ignore_errors=True)


def pytest_unconfigure(config):
    # an xdist worker leaves through os._exit and runs no atexit
    shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.cluster_utils import Cluster  # noqa: E402

_time_scale: list = []


def time_scale(fresh: bool = False) -> float:
    """Deadline multiplier for wall-clock-sensitive polls (VERDICT r4
    weak #1: a loaded 1-core host needs wider recovery margins).

    Measures this host's CURRENT effective speed once per process with a
    short fixed CPU probe (~0.23s idle on the 1-core dev host) and
    stretches test deadlines proportionally when the host is contended —
    an idle host keeps ~1× deadlines, a saturated core gets up to 6×.
    Override with ``RTPU_TEST_TIME_SCALE``.

    ``fresh=True`` re-probes NOW instead of using the session-start
    measurement — for tests whose margin depends on contention at the
    moment they run (load can arrive mid-session).  A fresh probe never
    REPLACES the cached session value: a transient lull must not shrink
    every later test's deadlines.
    """
    env = os.environ.get("RTPU_TEST_TIME_SCALE")
    if env:
        return max(1.0, float(env))
    if fresh:
        return _probe_scale()
    if not _time_scale:
        _time_scale.append(_probe_scale())
    return _time_scale[0]


def _probe_scale() -> float:
    import time
    t0 = time.perf_counter()
    acc = 0
    for i in range(1_500_000):
        acc += i * i
    dt = time.perf_counter() - t0
    return min(6.0, max(1.0, dt / 0.2))


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


@pytest.fixture(autouse=True)
def _ensure_shutdown():
    """No test leaves its cluster, or what ``ray_tpu.init()`` did to this
    process's jax, to the next test of the worker.  ``init`` turns
    ``jax_include_full_tracebacks_in_locations`` off for good wherever a
    compile cache is in use (a Pallas kernel's cache key needs it: PR 22),
    and with it off a lowered program names other locations (nothing
    inside a forward-only ``jax.checkpoint`` keeps its scope): tests that
    read locations passed alone and failed in a worker that had run a
    cluster test before them (tests/test_named_scopes.py, the driver's
    run of PR 29)."""
    full_tracebacks = jax.config.jax_include_full_tracebacks_in_locations
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    if jax.config.jax_include_full_tracebacks_in_locations != full_tracebacks:
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          full_tracebacks)
