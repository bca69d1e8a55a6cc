"""Grouped matmul over ragged groups on the chip: ``jax.lax.ragged_dot``
(XLA's own TPU lowering) against megablox's Pallas ``gmm`` / ``tgmm``
(``jax.experimental.pallas.ops.tpu.megablox``) by tile, forward and both
backward products.  ``ops/moe.py gmm_tiling`` answers what wins here.

    chiprun -- python benchmarks/grouped_matmul_bench.py

with no argument is PR 27's table: the OLMoE training cell's expert
layer, 65,536 rows in 64 ragged groups, 2048 -> 1024 (gate, up) and
1024 -> 2048 (down), equal and Dirichlet-skewed counts, four tiles.  A
layer that holds a share of its experts (``--held``: the first H of
``--groups``, as ``ops/moe._megablox`` calls the kernels) and another
sweep are arguments (PR 62, the Qwen3-Next cell's gate / up and down):

    chiprun -- python benchmarks/grouped_matmul_bench.py --rows 163840 \\
        --groups 512 --held 64 --d 2048 --f 512 \\
        --counts benchmarks/counts/qwen3_next_window.json \\
        --tilings 512,1024,512 256,2048,512 128,2048,512

A tiling is (rows, d, f): the forward and the weights' gradient take it
as it stands and the rows' gradient, which contracts f and puts out d,
with its last two swapped, as ``_megablox_bwd`` hands them on.  A skew of
``even`` draws the counts multinomially from equal shares, as a router
that spreads its choices evenly gives them; ``--counts`` takes, in place
of skews, the counts a step's layers really saw (a JSON file with a list
``counts``, as ``benchmarks/routing_counts.py`` writes it: a share of the
experts trained alone draws the router onto itself, and the rows a group
holds grow sevenfold inside one window of the Qwen3-Next cell).

``--stack L`` (PR 66): beside each megablox measurement, the same three
products as a training scan's layer calls them since PR 66
(``ops/moe._megablox_at``): the weights are a STACK of ``L`` layers' held
experts, ``(L x H, d, f)``, read in place, and the counts are one layer's
(the middle one's) laid among the stack's groups, every other group empty
(``ops/moe._sizes_in_stack``); the weights' gradient is the layer's own
``tgmm`` either way.  What the empty groups cost is the difference of the
two lines.  With no ``--tilings`` it measures the tile ``gmm_tiling``
picks for the shape:

    chiprun -- python benchmarks/grouped_matmul_bench.py --stack 3 \
        --skews even                                        # OLMoE
    ... --stack 7 --rows 98304 --groups 128 --held 16 --d 2048 --f 768
    ... --stack 3 --rows 163840 --groups 512 --held 64 --d 2048 --f 512

Prints one JSON line a measurement and writes them all to
``chiprun_out/grouped_matmul_bench.jsonl`` (``--out``).  Each line has a
product's host-clock time (``*_ms``), the kernel's own device time from a
profiler capture (``*_kernel_ms``: what a cell's ``moe.*_peak_share``
divides), every other device operation's of the call
(``*_other_device_ms``: XLA's passes round a kernel that stands alone in
its program, and the operations that make megablox's metadata from the
counts, which is where a stack's empty groups cost) and, for megablox,
``visits`` (the (group, row tile) overlaps the kernel's grid walks,
``ops/moe.gmm_visits``) and ``fill`` (held rows / (visits x row tile): the share of the rows
multiplied that lay in the visit's own group).  Fails off the chip: a
time from a CPU is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from attention_bench import device_seconds  # noqa: E402
from ray_tpu.ops.moe import _sizes_in_stack, gmm_tiling, gmm_visits  # noqa: E402

PEAK = 197e12       # v5e bf16, perfbench/peaks.json


def group_sizes(rng, rows: int, groups: int, skew) -> np.ndarray:
    """Rows per group summing to ``rows``: multinomial over a Dirichlet
    draw (``skew`` 0: all equal; ``"even"``: multinomial over equal
    shares)."""
    if skew == 0:
        return np.full(groups, rows // groups, np.int32)
    p = np.full(groups, 1.0 / groups) if skew == "even" \
        else rng.dirichlet(np.full(groups, 1.0 / skew))
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


def kernel_seconds(fn, *args):
    """-> (the grouped-matmul kernels' own device seconds a call of ``fn``,
    every other device operation's: the counts' metadata, XLA's zeroing
    pass round a kernel over a held share)."""
    name, top, others = device_seconds(fn, *args)
    ops = [(name, top), *others]
    own = [s for n, s in ops
           if any(part in n.lower() for part in ("gmm", "custom", "ragged"))]
    kernels = sum(own) if own else top
    return kernels, sum(s for _, s in ops) - kernels


TILINGS = ((128, 128, 128), (512, 512, 512), (512, 1024, 1024),
           (1024, 1024, 1024))


def ragged_dot_products(held):
    """(forward, rows' gradient, weights' gradient) as jax.grad of
    ``ragged_dot`` computes them, over the ``held`` leading groups."""
    def fwd(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs[:held])

    def dlhs(dy, w, gs):
        return jax.lax.ragged_dot(dy, w.swapaxes(1, 2), gs[:held])

    def drhs(x, dy, gs):
        w0 = jnp.zeros((held, x.shape[1], dy.shape[1]), x.dtype)
        return jax.vjp(lambda w: fwd(x, w, gs), w0)[1](dy)[0]

    return fwd, dlhs, drhs


def megablox_products(tiling, held):
    """The same three as ``ops/moe._megablox`` calls them: ``w`` holds the
    ``held`` leading groups of those ``gs`` counts."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    rows, d, f = tiling

    def fwd(x, w, gs):
        return gmm(x, w, gs, x.dtype, (rows, d, f))

    def dlhs(dy, w, gs):
        return gmm(dy, w, gs, dy.dtype, (rows, f, d), transpose_rhs=True)

    def drhs(x, dy, gs):
        return tgmm(x.swapaxes(0, 1), dy, gs, x.dtype, (rows, d, f),
                    num_actual_groups=held)

    return fwd, dlhs, drhs


def stacked_products(tiling, held, layers):
    """``megablox_products`` as ``ops/moe._megablox_at`` calls them: ``w``
    holds ``layers`` layers' held groups and the counts ``gs`` are the
    middle layer's."""
    fwd, dlhs, drhs = megablox_products(tiling, held)

    def in_stack(gs):
        return _sizes_in_stack(gs, held, layers * held,
                               jnp.int32(layers // 2))

    return (lambda x, w, gs: fwd(x, w, in_stack(gs)),
            lambda dy, w, gs: dlhs(dy, w, in_stack(gs)), drhs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--held", type=int, default=None,
                    help="the first H groups have weights (default: all)")
    ap.add_argument("--d", type=int, default=None)
    ap.add_argument("--f", type=int, default=None,
                    help="with --d: one (d, f) product; default OLMoE's two")
    ap.add_argument("--skews", nargs="+", default=["0", "0.05", "1"],
                    help="Dirichlet skews of the counts, or 'even'")
    ap.add_argument("--counts", default=None,
                    help="a JSON file whose 'counts' lists (groups,) rows "
                    "a group, as a step's layers saw them: each is "
                    "measured, in place of --skews")
    ap.add_argument("--tilings", nargs="+", default=None,
                    help="megablox tiles 'rows,d,f' (default PR 27's four)")
    ap.add_argument("--no-ragged-dot", action="store_true")
    ap.add_argument("--stack", type=int, default=0,
                    help="also measure megablox over a stack of this many "
                    "layers' held experts read in place, one layer's "
                    "groups filled (ops/moe._megablox_at)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/grouped_matmul_bench.jsonl")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_matmul_bench measures a TPU; "
                         f"this is {jax.default_backend()!r}")
    held = args.groups if args.held is None else args.held
    shapes = ((2048, 1024), (1024, 2048)) if args.d is None \
        else ((args.d, args.f),)
    tilings = TILINGS if args.tilings is None else tuple(
        tuple(int(t) for t in text.split(",")) for text in args.tilings)
    ragged = [] if args.no_ragged_dot or args.stack else [
        ("ragged_dot", None, 1, ragged_dot_products(held))]
    rng = np.random.default_rng(args.seed)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    lines = []
    if args.counts:
        listed = json.loads(Path(args.counts).read_text())["counts"]
        drawn = [(f"counts[{i}]", np.asarray(c, np.int32))
                 for i, c in enumerate(listed)]
    else:
        drawn = [(skew, group_sizes(rng, args.rows, args.groups, skew))
                 for skew in (s if s == "even" else float(s)
                              for s in args.skews)]
    for skew, gs_host in drawn:
        assert gs_host.shape == (args.groups,) and gs_host.sum() == args.rows
        gs = jnp.asarray(gs_host)
        held_rows = int(gs_host[:held].sum())
        for d, f in shapes:
            key = jax.random.key(args.seed)
            x = jax.random.normal(key, (args.rows, d), jnp.bfloat16)
            dy = jax.random.normal(key, (args.rows, f), jnp.bfloat16)
            w = jax.random.normal(key, (max(args.stack, 1) * held, d, f),
                                  jnp.bfloat16)
            w_layer = w[:held]          # what a call without a stack reads
            flops = 2.0 * held_rows * d * f
            dense = timed(jax.jit(lambda a, b: a @ b), x, w[0])
            chosen = tilings if args.tilings or not args.stack \
                else (gmm_tiling(args.rows, d, f),)
            impls = ragged + [(f"megablox{t}", t, 1,
                               megablox_products(t, held)) for t in chosen]
            impls += [(f"megablox{t}", t, args.stack,
                       stacked_products(t, held, args.stack))
                      for t in chosen if args.stack]
            for name, tiling, layers, (fwd, dlhs, drhs) in impls:
                w_read = w if layers > 1 else w_layer
                row = {"impl": name, "rows": args.rows, "groups": args.groups,
                       "stack": layers,
                       "held": held, "held_rows": held_rows, "d": d, "f": f,
                       "skew": skew, "seed": args.seed,
                       "device": jax.devices()[0].device_kind,
                       "max_over_mean": float(gs_host.max() * args.groups
                                              / args.rows),
                       "dense_ms": dense * 1e3}
                if tiling:
                    row["visits"] = int(gmm_visits(gs_host, held, tiling[0]))
                    row["fill"] = held_rows / max(row["visits"] * tiling[0], 1)
                for what, fn, a in (("fwd", fwd, (x, w_read, gs)),
                                    ("dlhs", dlhs, (dy, w_read, gs)),
                                    ("drhs", drhs, (x, dy, gs))):
                    try:
                        fn = jax.jit(fn)
                        s = timed(fn, *a)
                        kernel, rest = kernel_seconds(fn, *a)
                        row[f"{what}_ms"] = s * 1e3
                        row[f"{what}_kernel_ms"] = kernel * 1e3
                        row[f"{what}_other_device_ms"] = rest * 1e3
                        row[f"{what}_peak_share"] = \
                            100 * flops / kernel / PEAK
                    except Exception as e:  # noqa: BLE001 - a tiling
                        # Mosaic refuses is a result, not a crash
                        row[f"{what}_error"] = repr(e)[:200]
                print(json.dumps(row), flush=True)
                lines.append(row)
    with out.open("a") as sink:
        sink.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
