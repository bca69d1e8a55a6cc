"""The flash kernels alone on the chip, at the three training cells' shapes:
microseconds a 512 x 512 tile beside what the MXU needs for the tile's
products.

    chiprun -- python benchmarks/attention_bench.py --kernel bwd
    chiprun -- python benchmarks/attention_bench.py --kernel fwd

``--kernel bwd`` times ``flash_bwd`` (``_flash_backward_flat``) and
``--kernel fwd`` ``flash_fwd`` with its lse, each on the operands the cell's
step hands it: GPT-2 XL (200 x 1,024 x 64, one part), OLMoE (32 x 4,096 x
128) and Kanana (64 x 8,192: latent attention's two parts, 128 + 64, the
rotary key ONE (2, 8,192, 64) array read by ``bh // 32``, values 128 wide).
A tile's MXU time is its products' operations (five in the backward, two in
the forward) at the published 197 TFLOP/s, ``mxu_us`` as executed and
``mxu_us_padded`` with a 64-wide contracted or result dimension counted as
the 128 it occupies.  Writes ``chiprun_out/attention_bench.<kernel>.jsonl``.
Fails off the chip: a time from a CPU is no device number.

GPT-2 XL's pass is also timed between its projections, a program of its own
in each of two layouts (``"layout"`` on the line, PR 53): ``split``, the
present kernels on (B.H, T, 64) operands cut out of a (B, T, 3, E)
projection, the output and the gradients laid back, XLA's copies round the
kernel counted (``other_device_ms``); ``pairs``, ``flash_fwd_pairs`` /
``flash_bwd_pairs`` on the (B, 3, T, E) projection as it stands, two heads a
128-lane block, 25 heads in 13 blocks.  ``us_a_tile`` there is a tile A HEAD
(200 x 3 of them, the half-empty thirteenth block's time spread over the 25).

Each line holds the kernel's own device time from a profiler capture (the
number a cell's ``kernels.custom_call_ms`` / ``mla.attention_ms`` divides),
what XLA puts round a kernel that stands alone in its program (copies of
64-wide operands: 0.69 ms a call at GPT-2 XL's shape, none at 128 lanes)
and the host's clock for the whole call.

Measured on the chip (TPU v5 lite, 2026-10-01, PR 50), us a tile, the
kernel's device time; the backward before and after its tile was turned
keys-down, the forward as it stands:

                   bwd before   bwd after   MXU, 64 -> 128   fwd    MXU
    GPT-2 XL         2.601        2.198         1.70         1.584  0.68
    OLMoE            2.494        2.125         1.70         1.260  0.68
    Kanana           3.424        3.221         2.73         1.507  1.02

Without ``--kernel``: fwd+bwd of ``flash_attention`` beside XLA's
``dense_attention`` per sequence length at ~8k tokens (``--seqs``), 12 heads
x 64, the comparison this file began with (``pick_block_size`` cites it;
same chip and day, ms a call):

    seq= 2048 b=4: dense   8.59   flash 1.97
    seq= 4096 b=2: dense  23.87   flash 3.13
    seq= 8192 b=1: dense 362.29   flash 5.42
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from perfbench import trace  # noqa: E402
from perfbench.device import peaks_for  # noqa: E402

# the module: the attribute ``ray_tpu.ops.flash_attention`` is the function
fa = importlib.import_module("ray_tpu.ops.flash_attention")

# (B, H, T, the queries' and keys' parts, the values' width, shared): the
# operands of one flash call in the cell's step; ``shared`` parts of the
# key are one (B, T, D_i) array for all heads.
CELLS = {
    "gpt2-xl-1558m.train-b8-s1024": (8, 25, 1024, (64,), 64, ()),
    "olmoe-1b-7b.train-b2-s4096": (2, 16, 4096, (128,), 128, ()),
    "kanana-2-30b-a3b.train-b2-s8192": (2, 32, 8192, (128, 64), 128, (1,)),
}


def operands(cell):
    """-> (qs, ks, v, do, lse, delta) in the kernels' flat layout, lse the
    forward's own so that the backward's probabilities are probabilities."""
    B, H, T, parts, dv, shared = CELLS[cell]
    keys = iter(jax.random.split(jax.random.key(0), 2 * len(parts) + 2))

    def draw(lead, d):
        return jax.random.normal(next(keys), (lead, T, d), jnp.bfloat16)
    qs = tuple(draw(B * H, d) for d in parts)
    ks = tuple(draw(B if i in shared else B * H, d)
               for i, d in enumerate(parts))
    v, do = draw(B * H, dv), draw(B * H, dv)
    bs = fa.pick_block_size(T)
    out, lse = jax.jit(lambda qs, ks, v: fa._flash_forward_lse_flat(
        qs, ks, v, causal=True, bs=bs, interpret=False))(qs, ks, v)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]
    return qs, ks, v, do, lse, delta


def tiles(cell):
    B, H, T, *_ = CELLS[cell]
    n = T // fa.pick_block_size(T)
    return B * H * n * (n + 1) // 2


def mxu_us(cell, kernel, padded):
    """What the MXU needs for one tile's products at the chip's peak."""
    _, _, T, parts, dv, _ = CELLS[cell]
    bs = fa.pick_block_size(T)
    width = fa._lanes if padded else int
    qk = sum(width(d) for d in parts)
    widths = 3 * qk + 2 * width(dv) if kernel == "bwd" else qk + width(dv)
    peak = peaks_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    return 2 * bs * bs * widths / peak * 1e6


def timed(fn, *args, seconds=0.5, sets=5):
    """Median over ``sets`` of the seconds a call of ``fn`` takes on the
    device: calls enqueued back to back, one wait at the end of a set."""
    jax.block_until_ready(fn(*args))          # compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    iters = max(4, int(seconds / max(time.perf_counter() - t0, 1e-5)))
    took = []
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t0) / iters)
    return statistics.median(took)


def device_seconds(fn, *args, calls=8):
    """-> (the Mosaic kernel's name in the trace, its seconds, every other
    device operation's name and seconds) a call of ``fn``, from a profiler
    capture of ``calls`` calls: the kernel's own time as a cell's
    ``mla.attention_ms`` / ``kernels.custom_call_ms`` read it, apart from
    what XLA puts round a kernel that stands alone in its program.  The
    kernel is the operation that takes most of the time."""
    jax.block_until_ready(fn(*args))          # compile
    with tempfile.TemporaryDirectory() as scratch:
        capture = trace.Capture(scratch)
        capture.start()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        capture.stop()
        ops = trace.top_ops(trace.load_window(capture), n=1000)
    (name, kernel), *others = ops
    return name, kernel / calls, [(n, s / calls) for n, s in others]


def kernel_call(cell, kernel):
    """The jitted call of one kernel on ``operands(cell)``."""
    bs = fa.pick_block_size(CELLS[cell][2])
    if kernel == "fwd":
        return jax.jit(lambda qs, ks, v, do, lse, delta:
                       fa._flash_forward_lse_flat(qs, ks, v, causal=True,
                                                  bs=bs, interpret=False))
    return jax.jit(lambda qs, ks, v, do, lse, delta:
                   fa._flash_backward_flat(qs, ks, v, lse, delta, do,
                                           causal=True, block_size=bs,
                                           interpret=False))


XL = "gpt2-xl-1558m.train-b8-s1024"


def in_the_model(kernel, layout):
    """-> (the jitted pass, its arguments): GPT-2 XL's attention between
    its projections, forward with its lse or backward from the output's
    cotangent (B, T, E) to the projection's, in one of the two layouts."""
    B, H, T, *_ = CELLS[XL]
    E = H * fa.HEAD
    k_qkv, k_g = jax.random.split(jax.random.key(0))
    g = jax.random.normal(k_g, (B, T, E), jnp.bfloat16)
    if layout == "pairs":
        qkv = jax.random.normal(k_qkv, (B, 3, T, E), jnp.bfloat16)

        def fwd(qkv):
            return fa._pairs_forward(qkv, H, None, False, want_lse=True)

        def bwd(qkv, out, lse, g):
            return fa._pairs_bwd(H, None, False, (qkv, out, lse), g)[0]
    else:
        qkv = jax.random.normal(k_qkv, (B, T, 3, E), jnp.bfloat16)

        def split(qkv):
            return [qkv[:, :, i].reshape(B, T, H, fa.HEAD) for i in range(3)]

        def fwd(qkv):
            q, k, v = split(qkv)
            out, lse = fa._flash_forward_lse((q,), (k,), v, causal=True,
                                             block_size=None, interpret=False)
            return out.reshape(B, T, E), lse

        def bwd(qkv, out, lse, g):
            q, k, v = split(qkv)
            res = ((q,), (k,), v, out.reshape(B, T, H, fa.HEAD), lse)
            dq, dk, dv = fa._bwd(True, None, False, res,
                                 g.reshape(B, T, H, fa.HEAD))
            return jnp.stack([d.reshape(B, T, E) for d in (dq, dk, dv)], 2)
    if kernel == "fwd":
        return jax.jit(fwd), (qkv,)
    out, lse = jax.jit(fwd)(qkv)
    return jax.jit(bwd), (qkv, out, lse, g)


def layouts_agree(kernel):
    """The two layouts on the SAME numbers (the split projection's planes
    transposed): the largest difference between their results beside the
    largest result, on the chip's own arithmetic."""
    split, args = in_the_model(kernel, "split")
    pairs, _ = in_the_model(kernel, "pairs")
    qkv, planes = args[0], args[0].transpose(0, 2, 1, 3)
    if kernel == "fwd":         # (out, lse): compare the outputs
        want, got = split(qkv)[0], pairs(planes)[0]
    else:                       # the projection's gradient, (B, T, 3, E)
        g = args[-1]
        out, lse = in_the_model("fwd", "pairs")[0](planes)   # its own lse
        want = split(*args)
        got = pairs(planes, out, lse, g).transpose(0, 2, 1, 3)
    want, got = want.astype(jnp.float32), got.astype(jnp.float32)
    return {"cell": XL, "kernel": kernel, "layouts": "pairs against split",
            "max_abs_difference": float(jnp.abs(got - want).max()),
            "max_abs": float(jnp.abs(want).max())}


def row(cell, kernel, fn, args, **tags):
    """One result line: the kernel's device time by the tile (device
    trace), the host's clock for a whole call beside it."""
    n = tiles(cell)
    name, kernel_s, others = device_seconds(fn, *args)
    us, padded = kernel_s * 1e6 / n, mxu_us(cell, kernel, True)
    return {"cell": cell, "kernel": kernel, **tags,
            "device": jax.devices()[0].device_kind, "tiles": n,
            "traced_as": name, "kernel_ms": kernel_s * 1e3,
            "other_device_ms": sum(s for _, s in others) * 1e3,
            "other_ops_ms": {n: round(s * 1e3, 4) for n, s in others[:6]},
            "host_clock_call_ms": timed(fn, *args) * 1e3, "us_a_tile": us,
            "mxu_us": mxu_us(cell, kernel, False), "mxu_us_padded": padded,
            "not_under_the_mxu_us": us - padded}


def by_sequence_length(args):
    from ray_tpu.ops.attention import dense_attention

    def bench(fn, q, k, v):
        g = jax.jit(jax.grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2)))
        try:
            return {"ms": round(timed(g, q, k, v) * 1e3, 2)}
        except Exception as e:  # noqa: BLE001 - OOM / compile limits
            return {"error": str(e)[:120]}

    for T in (int(s) for s in args.seqs.split(",")):
        B = max(1, args.tokens // T)
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = [jax.random.normal(kk, (B, T, args.heads, args.head_dim),
                                     jnp.bfloat16) for kk in ks]
        print(json.dumps({
            "seq": T, "batch": B,
            "dense": bench(lambda a, b, c: dense_attention(
                a, b, c, causal=True), q, k, v),
            "flash": bench(lambda a, b, c: fa.flash_attention(a, b, c, True),
                           q, k, v)}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", choices=("fwd", "bwd"))
    ap.add_argument("--seqs", default="2048,4096,8192")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=8192,
                    help="total tokens per step (batch = tokens/seq)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    if args.kernel is None:
        return by_sequence_length(args)
    rows = [row(cell, args.kernel, kernel_call(cell, args.kernel),
                operands(cell)) for cell in CELLS]
    rows += [row(XL, args.kernel, *in_the_model(args.kernel, layout),
                 layout=layout) for layout in ("split", "pairs")]
    rows.append(layouts_agree(args.kernel))
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    with open(out / f"attention_bench.{args.kernel}.jsonl", "w") as f:
        for r in rows:
            print(json.dumps(r), flush=True)
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
