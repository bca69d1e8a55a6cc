"""The dropless layer's two row passes alone on the chip, at the Kanana
cell's shape: XLA's gathers over all N k rows beside the kernels whose
work list ends at ``held_rows`` (``ops/moe.py``: ``spread_held_rows``,
``sum_held_slots``), at three held shares.

    chiprun -- python benchmarks/moe_rows_bench.py
    chiprun -- python benchmarks/moe_rows_bench.py --in-flight 16,32,64

(``--in-flight``: the way back's copies under way, a power of two.)

N 16,384 tokens, k 6 of 128 experts, d 2,048, bfloat16: 98,304 sorted rows
of 4,096 B.  The routing is drawn (6 distinct experts a token, uniform) and
sorted by ``_sorted_assignments`` as a layer sorts it; ``held_rows`` is the
rows of the first 16, 26 and all 128 experts: an eighth, a fifth and every
row.  Each line holds one pass one way: the pass's own device time from a
profiler capture (the operation that takes most of the time: XLA's gather
fusion or the kernel), every other device operation of the call (XLA: the
reduce over the k slots; the kernels: ``order % N``, the sort that lists the
held assignments by token), nanoseconds a visited row, and how far the
kernel's result is from XLA's on the rows and tokens it answers for (0: the
same bits).  A last line runs one whole layer (16 of 128 experts held, 768
wide) forward and backward both ways and counts the values that differ (0:
the rows the way out leaves unwritten reach nothing).  Writes
``chiprun_out/moe_rows_bench.jsonl``.  Fails off the chip: a time from a
CPU is no device number.

Measured on the chip (TPU v5 lite, 2026-10-02, PR 56), ms a call, the pass's
own operation (+ the other operations of its call); ns a visited row:

                       XLA, all rows     1/8 held      1/5 held      every row
    spread (way out)   0.50  (5 ns)*     0.26 (21)     0.35 (18)     1.27 (13)
    sum (way back)     3.37 + 0.70 (34)  0.66 + 0.11   1.00 + 0.11   4.39 + 0.11
                                         (54)          (50)          (45)

* XLA put the stand-alone program's (N, d) source in VMEM; in the cell's
step one of the three spreads reads it from HBM at 3.34 ms a call, as the
sum's gather does here (PERF.md sections 6 and 7).  The way out as first
written fetched the aligned group of every row, as the way back does: 0.64,
1.03 and 4.95 ms (52, 51, 50 ns a row), the same with 16, 32 or 64 copies
under way: the memory's speed at 8 times the bytes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from attention_bench import device_seconds  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

E = 128
HELD = (16, 26, 128)


def drawn(n, k, d, seed=0):
    """-> (x (N, d), rows (N k, d), order, inverse, group_sizes (E,))."""
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
    rows = jax.random.normal(keys[1], (n * k, d), jnp.bfloat16)
    expert_idx = jnp.argsort(jax.random.uniform(keys[2], (n, E)), axis=-1
                             )[:, :k].astype(jnp.int32)
    weights = jnp.ones((n, k), jnp.float32)
    order, inverse, _, sizes = jax.jit(
        lambda e, w: moe._sorted_assignments(e, w, E, 0))(expert_idx, weights)
    return x, rows, order, inverse, sizes


def line(which, way, fn, args, visited, **tags):
    name, own, others = device_seconds(fn, *args)
    return {"pass": which, "way": way, **tags,
            "device": jax.devices()[0].device_kind, "traced_as": name,
            "pass_ms": own * 1e3,
            "other_device_ms": sum(s for _, s in others) * 1e3,
            "other_ops_ms": {n: round(s * 1e3, 4) for n, s in others[:6]},
            "rows_visited": visited, "ns_a_visited_row": own * 1e9 / visited}


def layer_bits(n, k, d, f=768, held=16, seed=0):
    """One layer that holds ``held`` of E experts, forward and backward,
    through the kernels and through XLA's gathers: how many values of the
    output and of each gradient differ (the rows behind ``held_rows`` are
    not written on the kernels' way, and nothing may read them)."""
    keys = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
    expert_idx = jnp.argsort(jax.random.uniform(keys[1], (n, E)), axis=-1
                             )[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (n, k), jnp.float32, 0.05, 0.5)
    w_gate, w_up = (jax.random.normal(key, (held, d, f), jnp.bfloat16) * 0.02
                    for key in keys[3:5])
    w_down = jax.random.normal(keys[5], (held, f, d), jnp.bfloat16) * 0.03
    cotangent = jax.random.normal(keys[6], (n, d), jnp.float32)

    def layer(x, weights, *ws):
        y = moe.dropless_experts(x, expert_idx, weights, *ws,
                                 num_experts=E)[0]
        return (y.astype(jnp.float32) * cotangent).sum(), y
    walks, results = moe._walks_held_rows, []
    for kernels in (False, True):
        moe._walks_held_rows = lambda *a: kernels and walks(*a)
        jax.clear_caches()
        (_, y), grads = jax.jit(jax.value_and_grad(
            layer, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, weights, w_gate, w_up, w_down)
        results.append([a.astype(jnp.float32) for a in (y, *grads)])
    moe._walks_held_rows = walks
    names = ("y", "dx", "dweights", "dw_gate", "dw_up", "dw_down")
    return {"pass": "layer", "way": "kernels against xla", "seed": seed,
            "device": jax.devices()[0].device_kind,
            "values": {name: int(a.size) for name, a in zip(names,
                                                            results[0])},
            "differ": {name: int((a != b).sum())
                       for name, a, b in zip(names, *results)},
            "finite": all(bool(jnp.isfinite(b).all()) for b in results[1])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--k", type=int, default=6)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--in-flight", default=str(moe._IN_FLIGHT),
                    help="copies under way, a comma list to compare")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    n, k, d = args.tokens, args.k, args.d
    x, rows, order, inverse, sizes = drawn(n, k, d)
    spread = jax.jit(moe._spread_rows)
    sums = jax.jit(moe._sum_slots, static_argnums=2)
    out = [line("spread", "xla", spread, (x, order), n * k),
           line("sum", "xla", sums, (rows, inverse, k), n * k)]
    every = jnp.arange(n * k)[:, None]
    for in_flight in (int(w) for w in args.in_flight.split(",")):
        moe._IN_FLIGHT = in_flight
        jax.clear_caches()
        for held in HELD:
            held_rows = sizes[:held].sum()
            visited = int(held_rows)
            tags = {"held_share": visited / (n * k), "in_flight": in_flight}
            got = spread(x, order, held_rows)
            want = spread(x, order)
            tags["max_abs_difference"] = float(jnp.abs(jnp.where(
                every < held_rows, got.astype(jnp.float32)
                - want.astype(jnp.float32), 0)).max())
            out.append(line("spread", "kernel", spread,
                            (x, order, held_rows), visited, **tags))
            got = sums(rows, inverse, k, held_rows)
            want = sums(jnp.where(every < held_rows, rows, 0), inverse, k)
            tags["max_abs_difference"] = float(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32)).max())
            out.append(line("sum", "kernel", sums,
                            (rows, inverse, k, held_rows), visited, **tags))
    out.append(layer_bits(n, k, d))
    path = Path("chiprun_out")
    path.mkdir(exist_ok=True)
    with open(path / "moe_rows_bench.jsonl", "w") as f:
        for r in out:
            print(json.dumps(r), flush=True)
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
