"""(builder) The serving check of the SDAR cell on one seed with its
control.

    chiprun -- python benchmarks/sdar_check.py --seed 11

Builds the cell's engine with the seed's weights, runs
``perfbench.jobs.serve.Served.check_logits`` at the cell's own lengths
through the family's own stepping (``perfbench/families/sdar.py``: a prompt
of ``check_prompt_tokens`` = 102 tokens, its 25 whole blocks prefilled under
the block-causal mask, then ``check_decode_steps`` = 3 whole blocks, every
denoise pass and every commit pass of each against one plain forward over
what that pass was fed) and then again with the reference handed the
weights rounded to float8_e4m3, the nearest precision below the
configuration's, which the cell's limits have to fail.  One seed a process;
one JSON line, appended to ``chiprun_out/sdar_check.jsonl``: how the three
limits' ``why_`` of ``perfbench/configs/sdar-30b-a3b-chat.json`` are
reproduced.
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

CELL = "sdar-30b-a3b-chat.serve-fixedgen-blocks"


def rounded_to_float8(params, wide):
    """Every leaf the forward casts to its dtype in float8_e4m3 and back,
    the leaves it uses as stored (``wide``: the norms' scales) as they
    are; rounded on the
    host (on the device XLA drops a convert to a narrower type and back)
    and left there: the reference widens a layer at a time."""
    import jax
    import ml_dtypes
    import numpy as np

    def low(path, a):
        if any(getattr(k, "key", None) in wide for k in path):
            return a
        host = np.asarray(a)
        return host.astype(ml_dtypes.float8_e4m3fn).astype(host.dtype)

    return jax.tree_util.tree_map_with_path(low, params)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    out = Path("chiprun_out") / "sdar_check.jsonl"
    out.parent.mkdir(exist_ok=True)
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
            low = rounded_to_float8(served.params,
                                    served.fam.module().WIDE_PARAMS)
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
