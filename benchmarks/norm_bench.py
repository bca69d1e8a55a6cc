"""The DeltaNet mixer's gated output norm alone on the chip, at the
Qwen3-Next cell's shape (2 x 8,192 positions, 32 value heads of 128, bf16):
device time of one forward and of one forward-and-backward from a profiler
capture, for the Pallas kernels the cell runs (``ops/ssm.gated_rms_norm``)
and for the XLA form that defines them, beside the share of the v5e's 819
GB/s that the bytes a pass must move are of its time, and how far the two
forms differ on the same numbers.

    chiprun -- python benchmarks/norm_bench.py [rows=512 lanes=512 step=32 ...]

An argument sets one of the kernels' block sizes (``NORM_ROWS``,
``NORM_LANES``, ``NORM_STEP``); several of a name, separated by commas,
are tried one after another.  Prints one JSON line a variant and appends
them to ``chiprun_out/norm_bench.jsonl``.  Fails off the chip.
"""

from __future__ import annotations

import itertools
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

B, T, H, DV, EPS = 2, 8192, 32, 128, 1e-6
HBM_BYTES_PER_S = 819e9
# what a pass has to move, bf16: the forward reads o and z and writes y; the
# backward reads o, z and dy and writes do and dz
BYTES = {"fwd": 3 * B * T * H * DV * 2, "fwd_bwd": 8 * B * T * H * DV * 2}
SIZES = {"rows": "NORM_ROWS", "lanes": "NORM_LANES", "step": "NORM_STEP"}
RUNS = 3


def main() -> None:
    import os
    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import trace
    from ray_tpu.ops import ssm

    if jax.default_backend() != "tpu":
        raise SystemExit("norm_bench measures a TPU")
    asked = dict(a.split("=") for a in sys.argv[1:])
    grid = [[(name, int(v)) for v in asked[name].split(",")]
            for name in SIZES if name in asked]
    keys = jax.random.split(jax.random.key(0), 4)
    o = jax.random.normal(keys[0], (B, T, H * DV)).astype(jnp.bfloat16)
    z = (2 * jax.random.normal(keys[1], (B, T, H * DV))).astype(jnp.bfloat16)
    scale = jax.random.uniform(keys[2], (DV,), jnp.float32, 0.5, 1.5)
    # a cotangent that differs by head and by lane, as the output
    # projection hands one back
    probe = jax.random.normal(keys[3], (1, 1, H * DV)).astype(jnp.bfloat16)

    def xla(o, z, scale):
        return ssm._gated_rms_norm_xla(
            o.reshape(B, T, H, DV), z.reshape(B, T, H, DV), scale, EPS
        ).reshape(o.shape)

    def measured(form, label):
        fwd = jax.jit(form)
        # the value too, so that the forward runs in both forms (the
        # kernels' backward needs nothing of it)
        both = jax.jit(jax.value_and_grad(
            lambda o, z, s: (form(o, z, s) * probe).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        row = {"variant": label}
        results = [fwd(o, z, scale), *both(o, z, scale)[1]]
        jax.block_until_ready(results)
        for name, fn in (("fwd", fwd), ("fwd_bwd", both)):
            with tempfile.TemporaryDirectory() as d:
                capture = trace.Capture(d)
                capture.start()
                for _ in range(RUNS):
                    jax.block_until_ready(fn(o, z, scale))
                capture.stop()
                traced = trace.load_window(capture)
            ms = trace.busy_seconds(traced) * 1e3 / RUNS
            row[f"{name}_ms"] = ms
            row[f"{name}_hbm_share"] = BYTES[name] / HBM_BYTES_PER_S / ms * 1e3
            for kernel in ("gated_norm_fwd", "gated_norm_bwd"):
                row[f"{name}_{kernel}_ms"] = \
                    trace.op_seconds(traced, kernel) * 1e3 / RUNS
            row[f"{name}_top_ops_ms"] = [
                [n, round(s * 1e3 / RUNS, 3)]
                for n, s in trace.top_ops(traced, 6)]
        return row, [np.asarray(r, np.float32) for r in results]

    out = Path("chiprun_out") / "norm_bench.jsonl"
    out.parent.mkdir(exist_ok=True)

    def report(row):
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")

    row, want = measured(xla, "xla")
    report(row)
    for sizes in itertools.product(*grid):
        for name, value in sizes:
            setattr(ssm, SIZES[name], value)
        blocks = dict(rows=ssm.NORM_ROWS, lanes=ssm.NORM_LANES,
                      step=ssm.NORM_STEP)
        try:
            row, got = measured(
                lambda o, z, s: ssm.gated_rms_norm(o, z, s, EPS), "kernels")
        except Exception as e:  # noqa: BLE001 - Mosaic refused these blocks
            report({"variant": "kernels", **blocks, "refused": repr(e)[:300]})
            continue
        row.update(blocks)
        for name, a, b in zip(("y", "do", "dz", "dscale"), got, want):
            row[f"{name}_max_abs_diff_over_max"] = float(
                np.abs(a - b).max() / np.abs(b).max())
        report(row)


if __name__ == "__main__":
    main()
