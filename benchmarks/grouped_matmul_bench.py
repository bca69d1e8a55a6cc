"""Grouped matmul over ragged groups on the chip: ``jax.lax.ragged_dot``
(XLA's own TPU lowering) against megablox's Pallas ``gmm`` / ``tgmm``
(``jax.experimental.pallas.ops.tpu.megablox``), at the shape of the
OLMoE training cell's expert layer: 65,536 rows in 64 ragged groups,
2048 -> 1024 (gate, up) and 1024 -> 2048 (down), forward and both
backward products.  ``ops/moe.py grouped_matmul`` uses what wins here.

    chiprun -- python benchmarks/grouped_matmul_bench.py

Prints one JSON line a measurement and writes them all to
``chiprun_out/grouped_matmul_bench.jsonl``.  Fails off the chip: a time
from a CPU is no device number.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

PEAK = 197e12       # v5e bf16, perfbench/peaks.json


def group_sizes(rng, rows: int, groups: int, skew: float) -> np.ndarray:
    """Rows per group summing to ``rows``: multinomial over a Dirichlet
    draw (``skew`` 0: all equal)."""
    if skew == 0:
        return np.full(groups, rows // groups, np.int32)
    p = rng.dirichlet(np.full(groups, 1.0 / skew))
    return rng.multinomial(rows, p).astype(np.int32)


def timed(fn, *args, iters: int = 10):
    out = fn(*args)
    jax.block_until_ready(out)          # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


TILINGS = ((128, 128, 128), (512, 512, 512), (512, 1024, 1024),
           (1024, 1024, 1024))


def ragged_dot_products():
    """(forward, rows' gradient, weights' gradient) as jax.grad of
    ``ragged_dot`` computes them."""
    def fwd(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs)

    def dlhs(dy, w, gs):
        return jax.lax.ragged_dot(dy, w.swapaxes(1, 2), gs)

    def drhs(x, dy, gs):
        w0 = jnp.zeros((gs.shape[0], x.shape[1], dy.shape[1]), x.dtype)
        return jax.vjp(lambda w: jax.lax.ragged_dot(x, w, gs), w0)[1](dy)[0]

    return fwd, dlhs, drhs


def megablox_products(tiling):
    """The same three as megablox's custom vjp computes them."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    def fwd(x, w, gs):
        return gmm(x, w, gs, x.dtype, tiling)

    def dlhs(dy, w, gs):
        return gmm(dy, w, gs, dy.dtype, tiling, transpose_rhs=True)

    def drhs(x, dy, gs):
        return tgmm(x.swapaxes(0, 1), dy, gs, x.dtype, tiling)

    return fwd, dlhs, drhs


def implementations():
    return [("ragged_dot", ragged_dot_products())] + [
        (f"megablox{t}", megablox_products(t)) for t in TILINGS]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_matmul_bench measures a TPU; "
                         f"this is {jax.default_backend()!r}")
    rng = np.random.default_rng(args.seed)
    out = Path("chiprun_out") / "grouped_matmul_bench.jsonl"
    out.parent.mkdir(exist_ok=True)
    lines = []
    for skew in (0.0, 0.05, 1.0):
        gs_host = group_sizes(rng, args.rows, args.groups, skew)
        gs = jnp.asarray(gs_host)
        for d, f in ((2048, 1024), (1024, 2048)):
            key = jax.random.key(args.seed)
            x = jax.random.normal(key, (args.rows, d), jnp.bfloat16)
            dy = jax.random.normal(key, (args.rows, f), jnp.bfloat16)
            w = jax.random.normal(key, (args.groups, d, f), jnp.bfloat16)
            flops = 2.0 * args.rows * d * f
            dense = timed(jax.jit(lambda a, b: a @ b), x, w[0])
            for name, (fwd, dlhs, drhs) in implementations():
                row = {"impl": name, "d": d, "f": f, "skew": skew,
                       "max_over_mean": float(gs_host.max() * args.groups
                                              / args.rows),
                       "dense_ms": dense * 1e3}
                for what, fn, a in (("fwd", fwd, (x, w, gs)),
                                    ("dlhs", dlhs, (dy, w, gs)),
                                    ("drhs", drhs, (x, dy, gs))):
                    try:
                        s = timed(jax.jit(fn), *a)
                        row[f"{what}_ms"] = s * 1e3
                        row[f"{what}_peak_share"] = 100 * flops / s / PEAK
                    except Exception as e:  # noqa: BLE001 - a tiling
                        # Mosaic refuses is a result, not a crash
                        row[f"{what}_error"] = repr(e)[:200]
                print(json.dumps(row), flush=True)
                lines.append(row)
    out.write_text("".join(json.dumps(x) + "\n" for x in lines))


if __name__ == "__main__":
    main()
