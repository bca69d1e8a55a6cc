"""(builder) The serving check of the MiniCPM-SALA cell on several seeds,
and its control: the same check against the float32 reference computed from
the weights rounded to float8_e4m3, the nearest precision below the
configuration's, which ``serve.logit_atol`` has to fail.

    chiprun -- python benchmarks/minicpm_sala_check.py --seed 11
    chiprun -- python benchmarks/minicpm_sala_check.py --seed 11 --fault worst

With ``--fault`` the PROGRAM's selection is broken on purpose before the
engine is built (``forced-only``: the best-scoring blocks are dropped and
only the first block and the local ones are read; ``worst``: the top-k of
the negated scores), and the same check says whether ``serve.logit_atol``
sees it.

One seed a process (the engine and the reference fill the chip: a second
engine beside what the first leaves does not fit).  It builds the cell's
engine with the seed's weights, runs ``perfbench.jobs.serve.Served.check_logits`` at the cell's own
lengths (a prompt of ``check_prompt_tokens`` through the chunked prefill,
``check_decode_steps`` paged decode steps) and then again with the
reference handed the rounded tree.  One JSON line, appended to
``chiprun_out/minicpm_sala_check.jsonl``.  Fails off the chip: what bf16
does to a logit is the chip's arithmetic.
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

CELL = "minicpm-sala-9b.serve-longdoc-sparse"


def rounded_to_float8(params):
    """Every matrix of the tree in float8_e4m3 and back, the norms' scales
    as they are; rounded on the host (on the device XLA drops a convert to
    a narrower type and back) and left there: the reference widens a layer
    at a time, and two trees do not fit the chip."""
    import jax
    import ml_dtypes
    import numpy as np

    def low(path, a):
        if "scale" in jax.tree_util.keystr(path):
            return a
        host = np.asarray(a)
        return host.astype(ml_dtypes.float8_e4m3fn).astype(host.dtype)

    return jax.tree_util.tree_map_with_path(low, params)


def plant(fault: str) -> None:
    """Break ``ops.sparse_attention.choose_blocks`` for every program built
    after this call: the chunk's mask and the decode step's list both come
    from it."""
    import jax.numpy as jnp
    from ray_tpu.ops import sparse_attention

    sound = sparse_attention.choose_blocks

    def forced_only(logits, t, spec):
        # forced blocks come first in the list: cut the count to them
        ids, count = sound(logits, t, spec)
        first_local = jnp.maximum(
            jnp.maximum(t + 1 - spec.window, 0) // spec.block,
            spec.init_blocks)
        forced = spec.init_blocks + jnp.maximum(
            t // spec.block - first_local + 1, 0)
        return ids, jnp.minimum(count, forced)

    def worst(logits, t, spec):
        return sound(-logits, t, spec)

    sparse_attention.choose_blocks = {"forced-only": forced_only,
                                      "worst": worst}[fault]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--fault", choices=("forced-only", "worst"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from perfbench import run as runner
    out = Path("chiprun_out") / "minicpm_sala_check.jsonl"
    out.parent.mkdir(exist_ok=True)
    seed, args.seconds, args.trace = args.seed, 0.0, 0
    if args.fault:
        plant(args.fault)
        args.no_control = True
    _, _, ctx = runner.prepare(args)
    from perfbench.jobs import serve
    t0 = time.perf_counter()
    served = serve.Served(ctx)
    try:
        t1 = time.perf_counter()
        row = {"seed": seed, "setup_s": t1 - t0,
               args.fault or "sound": served.check_logits(seed)}
        row["check_s"] = time.perf_counter() - t1
        if not args.no_control:
            low = rounded_to_float8(served.params)
            plain = served.fam.reference_logits
            served.fam.reference_logits = \
                lambda params, tokens, config: plain(low, tokens, config)
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
