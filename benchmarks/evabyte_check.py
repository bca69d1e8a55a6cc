"""(builder) The serving check of the EvaByte cell on one seed, with its
control and with the fold broken on purpose.

    chiprun -- python benchmarks/evabyte_check.py --seed 11 [--heads]
    chiprun -- python benchmarks/evabyte_check.py --seed 11 --fault uniform_a

Builds the cell's engine with the seed's weights and steps ONE prompt as
``perfbench.jobs.serve`` does at the cell's own lengths (a prompt of
``check_prompt_tokens`` = 4,090 bytes through two chunks: the first window
folded by its chunk, 2,042 positions of the second left open; its held rows
scattered into the pool; then ``check_decode_steps`` = 8 steps through the
paged cache, of which the sixth's byte closes the second window INSIDE
``cache.append_slot`` and the last two read it folded).  The rows the program
made are then judged, by the job's own ``_judge``, against

* the plain reference (``sound``);
* the reference handed the weights rounded to float8_e4m3, the nearest
  precision below the configuration's, which the cell's limit has to fail
  (``float8``);
* the reference with its fold broken, one fault at a time
  (``reference/evabyte_ref.FAULTS``: a plain mean in place of the chunk's
  softmax; ``mu`` left out; the newest closed window's rows left out; a
  window's folded rows visible one window early; the open window cut at
  2,047): what a comparison of logits can see of each.  A fault of
  visibility is a mask on the reference's side: the difference between a
  sound program and a broken reference is the difference between a broken
  program and a sound reference, and the program's rows are made once.

With ``--fault uniform_a`` / ``no_mu`` instead: the check alone with the
PROGRAM's fold broken (``ops/eva_attention.fold_rows`` patched before its
programs are traced), to show that the two sides read alike.  With
``--heads``: ``models/llama.forward`` over the check's final sequence at the
published widths, every one of the 8 x 320 logits a position against the
reference's (heads 1-7 are otherwise the CPU tests' alone).  With
``--fold-time``: the fold program alone on the idle engine, five times over
the pool's first 32 pages, each waited for: what a close costs between two
decode steps.  One seed a
process; one JSON line, appended to ``chiprun_out/evabyte_check.jsonl``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "evabyte-6.5b.serve-bytes-longfile"
PROGRAM_FAULTS = ("uniform_a", "no_mu")


@contextlib.contextmanager
def broken(kind: str):
    """``ops/eva_attention.fold_rows`` under the fault ``kind``, for
    programs traced inside the context (a prompt's chunks and the fold of a
    window that closes in decode both call it)."""
    import jax.numpy as jnp

    from ray_tpu.ops import eva_attention as eva
    sound = eva.fold_rows

    def fold_rows(k, v, phi, mu, chunk):
        if kind == "uniform_a":
            # a softmax of zeros: the plain mean
            return sound(k, v, jnp.zeros_like(phi), mu, chunk)
        return sound(k, v, phi, jnp.zeros_like(mu), chunk)      # "no_mu"

    eva.fold_rows = fold_rows
    try:
        yield
    finally:
        eva.fold_rows = sound


def rounded_to_float8(params, wide):
    """Every leaf the forward casts to its dtype in float8_e4m3 and back,
    the leaves it uses as stored (``wide``: the norms' scales) as they are;
    rounded on the host and left there."""
    import jax
    import ml_dtypes
    import numpy as np

    def low(path, a):
        if any(getattr(k, "key", None) in wide for k in path):
            return a
        host = np.asarray(a)
        return host.astype(ml_dtypes.float8_e4m3fn).astype(host.dtype)

    return jax.tree_util.tree_map_with_path(low, params)


def judged(served, compared, **reference) -> dict:
    """The job's verdict on rows already made, against the reference called
    with ``reference`` (``params=``: other weights; ``fault=``)."""
    plain = served.fam.reference_logits
    weights = reference.pop("params", None)
    served.fam.reference_logits = lambda params, tokens, config: plain(
        params if weights is None else weights, tokens, config, **reference)
    try:
        return served._judge(compared)
    finally:
        served.fam.reference_logits = plain


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--fault", choices=PROGRAM_FAULTS)
    ap.add_argument("--heads", action="store_true")
    ap.add_argument("--fold-time", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    out = Path("chiprun_out") / "evabyte_check.jsonl"
    out.parent.mkdir(exist_ok=True)
    from perfbench import run as runner, traffic
    from perfbench.reference import evabyte_ref
    seed, args.seconds, args.trace = args.seed, 0.0, 0
    _, _, ctx = runner.prepare(args)
    from perfbench.jobs import serve
    t0 = time.perf_counter()
    row = {"seed": seed}
    with broken(args.fault) if args.fault else contextlib.nullcontext():
        served = serve.Served(ctx)
        try:
            import jax
            import numpy as np
            t1 = time.perf_counter()
            row["setup_s"] = t1 - t0
            spec = served.spec
            prompt = [int(t) for t in traffic.rng_for(seed, "serve_check")
                      .integers(0, served.config["vocab_size"],
                                spec["check_prompt_tokens"])]
            folded = served.eng.cache.windows_folded
            compared = served.stepping.check(served, prompt,
                                             spec["check_decode_steps"])
            row["windows_folded_in_decode"] = \
                served.eng.cache.windows_folded - folded
            name = f"program_fault_{args.fault}" if args.fault else "sound"
            row[name] = judged(served, compared)
            row["check_s"] = time.perf_counter() - t1
            if not args.fault:
                low = rounded_to_float8(served.params,
                                        served.fam.module().WIDE_PARAMS)
                row["float8"] = judged(served, compared, params=low)
                del low
                for fault in evabyte_ref.FAULTS:
                    row[f"reference_fault_{fault}"] = judged(
                        served, compared, fault=fault)
            if args.heads and not args.fault:
                fed = compared[0]["fed"]
                mod, mcfg = served.fam.module(), served.eng.runner.mcfg
                got = np.asarray(jax.jit(lambda p, t: mod.forward(
                    p, t, mcfg))(served.params, np.asarray([fed], np.int32)))
                want = served.fam.reference_logits(
                    served.params, [fed], served.config, heads=True)
                row["forward_heads_logit_diff"] = [
                    float(np.abs(got[0, :, h] - want[0, :, h]).max())
                    for h in range(got.shape[2])]
            cache, runner = served.eng.cache, served.eng.runner
            if args.fold_time and cache.fold_window:
                pages = np.arange(cache.fold_window // cache.block_size,
                                  dtype=np.int32)
                times = []
                for _ in range(5):
                    t2 = time.perf_counter()
                    runner.fold_windows(pages)
                    jax.block_until_ready(cache.pool.read(
                        lambda held: held["kv"][0, 0, 0, 0]))
                    times.append(1e3 * (time.perf_counter() - t2))
                row["fold_ms_host_clock"] = times
            stats = jax.devices()[0].memory_stats() or {}
            row["memory_stats"] = {k: stats[k] for k in (
                "peak_bytes_in_use", "bytes_in_use", "bytes_limit")
                if k in stats}
        finally:
            served.close()
    print(json.dumps(row), flush=True)
    with out.open("a") as f:
        f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
