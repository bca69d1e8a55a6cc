"""A Mamba-2 layer's training scan alone on the chip, at the Nemotron-H
cell's shape (2 x 8,192 positions, 64 heads of 64 on 8 groups of 128 state
columns, chunks of 128, the conv's float32 (2, 8192, 6144) result whole):
device time of one forward and of one forward-and-backward from a profiler
capture, for the Pallas kernels the cell runs (``ops/ssm.ssd_scan_train``)
and for ``ssd_scan`` under autodiff, which defines them, beside the share
of the v5e's bf16 peak that the products ``perfbench/flops_nemotron_h.py``
counts are of the time (one pass counted, six made), and how far the two
forms differ on the same numbers.

    chiprun -- python benchmarks/ssm_scan_bench.py [--seed N] [chunks=1,2,4]

``chunks`` sets ``SCAN_CHUNKS``, the chunks a grid step of both kernels
works through (without it, what the module has: the cell's); several
values are tried one after another and one Mosaic refuses is reported
and skipped.  Prints one JSON line a variant and appends them to
``chiprun_out/ssm_scan_bench.jsonl``.  Fails off the chip.
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

B, T, H, P, G, N, CHUNK = 2, 8192, 64, 64, 8, 128, 128
PEAK_FLOPS_PER_S = 197e12
SIZES = {"hybrid_override_pattern": "M", "mamba_num_heads": H,
         "mamba_head_dim": P, "n_groups": G, "ssm_state_size": N,
         "chunk_size": CHUNK}
RUNS = 3


def main() -> None:
    import os
    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import flops_nemotron_h, trace
    from ray_tpu.ops import ssm

    if jax.default_backend() != "tpu":
        raise SystemExit("ssm_scan_bench measures a TPU")
    args = sys.argv[1:]
    seed = int(args.pop(args.index("--seed") + 1)) if "--seed" in args else 0
    asked = dict(a.split("=") for a in args if "=" in a)
    chunks = [int(v) for v in asked["chunks"].split(",")] \
        if "chunks" in asked else [None]
    keys = jax.random.split(jax.random.key(seed), 5)
    # the conv's silu leaves about this; dt and A as Mamba-2 draws them
    xbc = jax.nn.silu(jax.random.normal(keys[0], (B, T, H * P + 2 * G * N)))
    dt = jnp.exp(jax.random.uniform(keys[1], (B, T, H), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(keys[2], (H,), jnp.float32, 1.0, 16.0)
    d = jax.random.uniform(keys[3], (H,), jnp.float32, 0.5, 1.5)
    probe = jax.random.normal(keys[4], (1, 1, H, P))
    operands = (xbc, dt, a, d)
    # the benchmark's count of a layer's products, a pass made once
    macs = flops_nemotron_h.scan_macs_per_token(SIZES) * B * T
    flops = {"fwd": 2 * macs, "fwd_bwd": 6 * macs}

    def xla(xbc, dt, a, d):
        return ssm._scan_train_xla(xbc, dt, a, d, P, G, CHUNK)

    def kernels(xbc, dt, a, d):
        assert ssm._scan_kernels_run(xbc, H, P, G, CHUNK)
        return ssm.ssd_scan_train(xbc, dt, a, d, P, G, CHUNK)

    def measured(form, label):
        # a function of its own each time: a jit of ``form`` itself would
        # hand a second variant the first's program
        fwd = jax.jit(lambda *ops: form(*ops))
        both = jax.jit(jax.value_and_grad(
            lambda *ops: (form(*ops) * probe).sum(), argnums=(0, 1, 2, 3)))
        row = {"variant": label, "seed": seed}
        results = [fwd(*operands), *both(*operands)[1]]
        jax.block_until_ready(results)
        for name, fn in (("fwd", fwd), ("fwd_bwd", both)):
            with tempfile.TemporaryDirectory() as tmp:
                capture = trace.Capture(tmp)
                capture.start()
                for _ in range(RUNS):
                    jax.block_until_ready(fn(*operands))
                capture.stop()
                traced = trace.load_window(capture)
            ms = trace.busy_seconds(traced) * 1e3 / RUNS
            row[f"{name}_ms"] = ms
            row[f"{name}_peak_share"] = \
                flops[name] / PEAK_FLOPS_PER_S / ms * 1e3
            for kernel in ("ssd_scan_fwd", "ssd_scan_bwd"):
                row[f"{name}_{kernel}_ms"] = \
                    trace.op_seconds(traced, kernel) * 1e3 / RUNS
            row[f"{name}_top_ops_ms"] = [
                [n, round(s * 1e3 / RUNS, 3)]
                for n, s in trace.top_ops(traced, 6)]
        return row, [np.asarray(r, np.float32) for r in results]

    out = Path("chiprun_out") / "ssm_scan_bench.jsonl"
    out.parent.mkdir(exist_ok=True)

    def report(row):
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")

    row, want = measured(xla, "xla")
    report(row)
    for m in chunks:
        if m is not None:
            ssm.SCAN_CHUNKS = dict.fromkeys(ssm.SCAN_CHUNKS, m)
        try:
            row, got = measured(kernels, "kernels")
        except Exception as e:  # noqa: BLE001 - Mosaic refused this step
            report({"variant": "kernels", "chunks": ssm.SCAN_CHUNKS,
                    "refused": repr(e)[:300]})
            continue
        row["chunks"] = ssm.SCAN_CHUNKS
        for name, g, w in zip(("y", "dxbc", "ddt", "da", "dd"), got, want):
            row[f"{name}_max_abs_diff_over_max"] = float(
                np.abs(g - w).max() / np.abs(w).max())
        report(row)


if __name__ == "__main__":
    main()
