"""(builder) The serving check of the Trinity cell on one seed with its
control, and the needle at the published head geometry.

    chiprun -- python benchmarks/afmoe_check.py --seed 11
    chiprun -- python benchmarks/afmoe_check.py --needle

``--seed``: builds the cell's engine with the seed's weights, runs
``perfbench.jobs.serve.Served.check_logits`` at the cell's own lengths (a
prompt of ``check_prompt_tokens`` through the chunked prefill, the last
chunk wholly past the window's edge, then ``check_decode_steps`` paged
decode steps) and then again with the reference handed the weights
rounded to float8_e4m3, the nearest precision below the configuration's,
which the cell's limits have to fail.  One seed a process; one JSON line,
appended to ``chiprun_out/afmoe_check.jsonl``.

``--needle``: the two window kernels at 48 query / 8 K/V heads of 128, a
window of 4,096 and blocks of 64 against plain ``jax.numpy``, on contrived
keys: one key whose score is far above the rest, with a value far from
the rest, just outside the window (it must change nothing) and just
inside (it must be all the output); decode at contexts that are no
multiple of the block, one of them the first step after a block went
back, and a prefill chunk whose queries straddle the needle's edge.  At
random weights a softmax over 4,096 keys is near flat and a window off by
a page moves a logit by less than bf16 does: the cell's check cannot see
it (perfbench/TRINITY.md).  Fails off the chip: it is the chip's kernels
that are asked.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "trinity-large-preview.serve-mixed-window"


def rounded_to_float8(params):
    """Every matrix of the tree in float8_e4m3 and back, the norms' scales
    and the selection bias as they are; rounded on the host (on the device
    XLA drops a convert to a narrower type and back) and left there: the
    reference widens a layer at a time, and two trees do not fit the chip."""
    import jax
    import ml_dtypes
    import numpy as np

    def low(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "expert_bias" in name:
            return a
        host = np.asarray(a)
        return host.astype(ml_dtypes.float8_e4m3fn).astype(host.dtype)

    return jax.tree_util.tree_map_with_path(low, params)


def needle() -> dict:
    """The window kernels against plain jax.numpy on contrived keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops import window_attention as wa
    assert jax.default_backend() == "tpu", "the chip's kernels are asked"
    H, KV, D, W, bs = 48, 8, 128, 4096, 64
    rng = np.random.default_rng(52)
    out = {}
    # ---- decode: contexts that are no multiple of the block; 8,230 is the
    # first step after a column went back (8,230 - 4,095 = 4,135 = 64 x 64
    # + 39: column 64 is the first, columns 0-63 are out of range)
    for ctx in (6000, 8230, 4159):
        maxb = 160
        n_blocks = maxb + 8
        lo = ctx - (W - 1)
        for where, pos in (("outside", lo - 1), ("inside", lo)):
            pool = rng.normal(0, 0.1, (1, 2, n_blocks, bs, KV * D)) \
                .astype(np.float32)
            table = rng.permutation(n_blocks)[:maxb].astype(np.int32)
            u = rng.normal(0, 1, (KV, D)).astype(np.float32)
            q = np.repeat(u, H // KV, axis=0)[None]          # (1, H, D)
            # the needle: score 3 |u|^2 / sqrt(D) ~ 34, value 100
            blk, off = table[pos // bs], pos % bs
            pool[0, 0, blk, off] = (3.0 * u).reshape(-1)
            pool[0, 1, blk, off] = 100.0
            given = table.copy()
            given[:lo // bs] = n_blocks          # given back: out of range
            args = (jnp.asarray(q), jnp.asarray(pool), 0,
                    jnp.asarray(given[None]), jnp.asarray([ctx], jnp.int32),
                    jnp.zeros((1, KV, D), jnp.float32),
                    jnp.zeros((1, KV, D), jnp.float32))
            kernel = np.asarray(pa.paged_attention_decode(*args, window=W))
            plain = np.asarray(pa._paged_decode_gather(*args, window=W))
            out[f"decode_ctx{ctx}_{where}"] = {
                "kernel_mean": float(kernel.mean()),
                "kernel_vs_plain": float(np.abs(kernel - plain).max())}
            if where == "outside":
                assert np.abs(kernel).max() < 1.0, (ctx, kernel.max())
                # the full layer sees it
                full = np.asarray(pa.paged_attention_decode(
                    args[0], args[1], 0, jnp.asarray(table[None]), *args[4:]))
                assert full.min() > 99.0, (ctx, full.min())
            else:
                assert kernel.min() > 99.0, (ctx, kernel.min())
            assert np.abs(kernel - plain).max() < 1e-3
    # ---- a prefill chunk: positions 8,192 .. 10,239 over the ring; the
    # needle at 5,120: query t sees it while t - 4,095 <= 5,120, that is
    # up to t = 9,215, and no query from 9,216 on
    C = 2048
    n = wa.ring_segments(W, C)
    index = 4
    chunk_of = wa.ring_chunks(jnp.int32(index), n)
    k_all = rng.normal(0, 0.1, (n * C, KV * D)).astype(np.float32)
    v_all = rng.normal(0, 0.1, (n * C, KV * D)).astype(np.float32)
    u = rng.normal(0, 1, (KV, D)).astype(np.float32)
    q = np.broadcast_to(u[None, :, None, :], (C, KV, H // KV, D)) \
        .astype(np.float32)
    at = (5120 // C) % n * C + 5120 % C
    k_all[at], v_all[at] = (3.0 * u).reshape(-1), 100.0
    args = (jnp.asarray(q, jnp.bfloat16), jnp.asarray(k_all),
            jnp.asarray(v_all), index * C, chunk_of)
    kernel = np.asarray(wa.chunk_attention(*args, W), np.float32)
    plain = np.asarray(wa._plain(*args, W), np.float32)
    edge = 9215 - index * C
    assert kernel[:edge + 1].min() > 99.0, kernel[:edge + 1].min()
    assert np.abs(kernel[edge + 1:]).max() < 1.0, kernel[edge + 1:].max()
    full = np.asarray(wa.chunk_attention(*args, None), np.float32)
    # (a full layer's staging is not a ring, but every position it holds is
    # behind the chunk: without the window every query sees the needle)
    assert full.min() > 99.0, full.min()
    out["prefill_chunk"] = {
        "last_query_that_sees": int(index * C + edge),
        "kernel_vs_plain": float(np.abs(kernel - plain).max()),
        "seen_min": float(kernel[:edge + 1].min()),
        "unseen_absmax": float(np.abs(kernel[edge + 1:]).max())}
    assert np.abs(kernel - plain).max() < 0.5        # bf16 of ~100
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int)
    ap.add_argument("--needle", action="store_true")
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    out = Path("chiprun_out") / "afmoe_check.jsonl"
    out.parent.mkdir(exist_ok=True)
    if args.needle:
        row = {"needle": needle()}
    else:
        from perfbench import run as runner
        seed, args.seconds, args.trace = args.seed, 0.0, 0
        _, _, ctx = runner.prepare(args)
        from perfbench.jobs import serve
        t0 = time.perf_counter()
        served = serve.Served(ctx)
        try:
            t1 = time.perf_counter()
            row = {"seed": seed, "setup_s": t1 - t0,
                   "sound": served.check_logits(seed)}
            row["check_s"] = time.perf_counter() - t1
            if not args.no_control:
                low = rounded_to_float8(served.params)
                plain = served.fam.reference_logits
                served.fam.reference_logits = \
                    lambda params, tokens, config, **kw: plain(
                        low, tokens, config, **kw)
                try:
                    row["float8"] = served.check_logits(seed)
                finally:
                    served.fam.reference_logits = plain
                    del low
            import jax
            row["memory_stats"] = {
                k: v for k, v in jax.devices()[0].memory_stats().items()
                if k in ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")}
        finally:
            served.close()
    print(json.dumps(row), flush=True)
    with out.open("a") as f:
        f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
