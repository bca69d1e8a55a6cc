"""Paged decode attention on the chip: the block-table kernel
(``ops/paged_attention._paged_decode_kernel``) against the gather-then-mask
path it replaces there, at the shape of the GPT-2 XL serving cell: 8 slots,
25 heads x 64, a float32 pool of 128 blocks of 16 positions, 48 layers.

    chiprun -- python benchmarks/paged_attention_bench.py

Each measurement is the attention of one decode step as ``forward_decode``
runs it: a scan over the layers, each handing the engine's whole pool
(``kv_cache.device_shape``) and its own index on, so whatever XLA does to
feed either path is inside the time.  At three fills (3 live chat
contexts, 8 live, the whole table), with the bytes
of K/V read as a share of the chip's 819 GB/s.  Also the largest
difference between the two paths' results on the chip (they are compared on
the CPU in ``tests/test_paged_attention.py``; the chip's matmuls are not
the CPU's).  Prints one JSON line a measurement and writes them all to
``chiprun_out/paged_attention_bench.jsonl``.  Fails off the chip: a time
from a CPU is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.serve.llm.kv_cache import device_shape  # noqa: E402

HBM_BYTES_PER_S = 819e9     # v5e, perfbench/peaks.json

# contexts of the 8 slots: what the chat cell holds on average (2-3 live
# of ~190), a full batch of its longest, and the whole table
FILLS = {
    "chat_3_live": [190, 62, 311, 0, 0, 0, 0, 0],
    "chat_8_live": [451, 357, 190, 84, 46, 128, 260, 402],
    "table_full": [1023] * 8,
}


def timed(fn, *args, iters: int = 20):
    jax.block_until_ready(fn(*args))          # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def attention_of_a_step(path):
    """q (L, B, H, D) and the engine's pool -> every layer's result, fed
    as ``forward_decode`` feeds it: the pool closed over by the scan, the
    layer's index scanned."""
    def step(pool, q, k_new, v_new, tables, lens):
        def body(_, xs):
            q, kn, vn, layer = xs
            return None, path(q, pool, layer, tables, lens, kn, vn)

        return lax.scan(body, None,
                        (q, k_new, v_new, jnp.arange(q.shape[0])))[1]
    return jax.jit(step)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    n, bs, h, d, b, maxb, layers = 128, 16, 25, 64, 8, 64, args.layers
    rng = np.random.default_rng(args.seed)
    # the lanes that pad 1,600 to 1,664 hold noise too: nothing reads them
    pool = jnp.asarray(rng.standard_normal(device_shape(n, layers, bs, h, d)),
                       jnp.float32)
    q, k_new, v_new = (jnp.asarray(rng.standard_normal((layers, b, h, d)),
                                   jnp.bfloat16) for _ in range(3))
    kernel = attention_of_a_step(pa._paged_decode_kernel)
    gather = attention_of_a_step(pa._paged_decode_gather)
    rows = []
    for name, lens in FILLS.items():
        # each row's blocks, distinct while the pool lasts; columns past
        # the context name arbitrary blocks
        tables = rng.integers(0, n, (b, maxb)).astype(np.int32)
        free = list(rng.permutation(n))
        for i, ctx in enumerate(lens):
            for j in range(-(-ctx // bs)):
                tables[i, j] = free.pop() if free else rng.integers(n)
        operands = (pool, q, k_new, v_new, jnp.asarray(tables),
                    jnp.asarray(lens, jnp.int32))
        got = np.asarray(kernel(*operands), np.float32)
        want = np.asarray(gather(*operands), np.float32)
        blocks = sum(-(-ctx // bs) for ctx in lens)
        read = blocks * 2 * layers * bs * h * d * 4
        t_kernel, t_gather = timed(kernel, *operands), timed(gather, *operands)
        rows.append({
            "fill": name, "device": dev.device_kind, "layers": layers,
            "blocks_read": blocks, "blocks_table": b * maxb,
            "kernel_ms": t_kernel * 1e3,
            "gather_ms": t_gather * 1e3,
            "kv_bytes_read": read,
            "kv_bytes_over_peak_ms": read / HBM_BYTES_PER_S * 1e3,
            "kernel_hbm_peak_share_pct":
                read / t_kernel / HBM_BYTES_PER_S * 100,
            "max_abs_diff": float(np.abs(got - want).max()),
            "finite": bool(np.isfinite(got).all()),
        })
        print(json.dumps(rows[-1]), flush=True)
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    with open(out / "paged_attention_bench.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
