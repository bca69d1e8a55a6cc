"""chip_smoke.py rehearsed on the CPU, and where the compile cache lives.

The rehearsal proves the script's control flow, not the chip: tiny sizes,
``JAX_PLATFORMS=cpu``, and a last line that can never be read as a pass.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# The parent's code runs in this wrapper, which then says on stderr
# whether jax was ever imported into it.
_PARENT = """
import runpy, sys
sys.argv = ["chip_smoke.py", "--rehearse"]
try:
    runpy.run_path("chip_smoke.py", run_name="__main__")
except SystemExit as e:
    code = e.code
print("JAX_IN_PARENT", "jax" in sys.modules, file=sys.stderr)
sys.exit(code)
"""


def test_rehearsal_runs_each_phase_in_a_process_of_its_own(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    proc = subprocess.run([sys.executable, "-c", _PARENT], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "JAX_IN_PARENT False" in proc.stderr
    lines = proc.stdout.splitlines()
    pids = [ln.split()[1] for ln in lines if ln.startswith("pid: ")]
    assert len(pids) == 5 and len(set(pids)) == 5       # parent + 4 phases
    for phase in ("native", "train", "framework", "serve"):
        assert f"===== phase {phase}" in proc.stdout
    assert f"compile_cache_dir: {tmp_path / 'xla'}" in proc.stdout
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert not any('"ok": true' in ln for ln in lines)


_INIT_AFTER_JAX = """
import jax, ray_tpu
ray_tpu.init(num_cpus=1)
print("CACHE_DIR", jax.config.jax_compilation_cache_dir)
print("FULL_TRACEBACKS", jax.config.jax_include_full_tracebacks_in_locations)
ray_tpu.shutdown()
"""


@pytest.mark.parametrize("placed", [True, False],
                         ids=["from_outside", "default"])
def test_compile_cache_dir(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache of a process that
    imported jax before init(); unset, the cache is a fixed directory of
    the checkout."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "RTPU_XLA_CACHE_DIR")}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", _INIT_AFTER_JAX], cwd=REPO,
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = [ln.split(" ", 1)[1] for ln in proc.stdout.splitlines()
           if ln.startswith("CACHE_DIR ")]
    assert got == [str(tmp_path if placed else REPO / ".xla_cache")]
    # or a program's cache key would depend on who called it
    assert "FULL_TRACEBACKS False" in proc.stdout


def test_init_counts_chips_from_device_nodes_not_from_jax(monkeypatch):
    """The driver must never start a backend to count chips: the TPU
    worker could then not open them."""
    import glob

    import ray_tpu
    nodes = {"/dev/accel[0-9]*": [], "/dev/vfio/[0-9]*": ["/dev/vfio/0"]}
    monkeypatch.setattr(glob, "glob", lambda pattern: nodes[pattern])
    monkeypatch.delenv("RTPU_NUM_TPUS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert ray_tpu._detect_tpu_chips() == 1.0
    nodes["/dev/accel[0-9]*"] = [f"/dev/accel{i}" for i in range(4)]
    assert ray_tpu._detect_tpu_chips() == 4.0
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert ray_tpu._detect_tpu_chips() == 0.0
    monkeypatch.setenv("RTPU_NUM_TPUS", "2")
    assert ray_tpu._detect_tpu_chips() == 2.0
