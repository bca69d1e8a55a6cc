"""(builder) The serving check of the Keye cell on one seed, with its
control and with the selection broken on purpose.

    chiprun -- python benchmarks/keye_check.py --seed 11 [--fault lowest]
    chiprun -- python benchmarks/keye_check.py --seed 11 --kernels

Builds the cell's engine with the seed's weights and runs
``perfbench.jobs.serve.Served.check_logits`` at the cell's own lengths (a
prompt of ``check_prompt_tokens`` = 6,144 tokens through three chunks, its
rows scattered into the K/V pool and the index plane, then
``check_decode_steps`` = 8 steps through the paged cache: two thirds of the
prompt's queries and every decode step choose among more than 2,048
positions); then again with the reference handed the weights rounded to
float8_e4m3, the nearest precision below the configuration's, which the
cell's limits have to fail.  With ``--fault <kind>`` instead: the check
alone with the PROGRAM's selection broken (:data:`FAULTS`) from the first
trace of its programs on (a second engine does not fit the chip beside
what the first leaves behind, so a fault is a process).  One seed a
process; one JSON line, appended to ``chiprun_out/keye_check.jsonl``: how the three
limits' ``why_`` of ``perfbench/configs/keye-vl-2.0-30b-a3b.json`` and the
table of ``perfbench/KEYE.md`` ("what `correct` cannot see") are
reproduced.

``--kernels``: no engine; the chunk's two Pallas kernels (the score pass,
which takes a float32 product as three bf16 passes, and the cut by
counting) and the decode row's cut at the cell's size, (2,048 queries or 4
rows) x 26,624 keys, against plain ``jax.numpy`` scores at full precision
cut by a stable sort on the host: the chosen SETS, position by position.
The keys are contrived: small whole numbers, so that every product is exact
at any precision, the scores are whole numbers with thousands of ties a
row, and a difference is a fault of a kernel and no rounding.
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

CELL = "keye-vl-2.0-30b-a3b.serve-longctx-indexed"
# the program's selection, broken: the top-k of the NEGATED scores; the
# first topk positions whatever the scores; topk - 1 of the right ones
FAULTS = ("lowest", "first", "short")


@contextlib.contextmanager
def broken(kind: str):
    """``ops/indexed_attention``'s cut (``topk_mask``: a run of queries
    takes the mask, a decode row's list is made from it) under the fault
    ``kind``, for programs traced inside the context."""
    import jax.numpy as jnp

    from ray_tpu.ops import indexed_attention as ix
    mask = ix.topk_mask

    def negated(scores):
        return jnp.where(jnp.isfinite(scores), -scores, scores)

    def topk_mask(scores, k):
        if kind == "lowest":
            return mask(negated(scores), k)
        if kind == "short":
            return mask(scores, k - 1)
        at = jnp.arange(scores.shape[-1])
        return jnp.isfinite(scores) & (at < k)              # "first"

    ix.topk_mask = topk_mask
    try:
        yield
    finally:
        ix.topk_mask = mask


def rounded_to_float8(params, wide):
    """Every leaf the forward casts to its dtype in float8_e4m3 and back,
    the leaves it uses as stored (``wide``: the norms' scales and biases)
    as they are; rounded on the host and left there: the reference widens a
    layer at a time."""
    import jax
    import ml_dtypes
    import numpy as np

    def low(path, a):
        if any(getattr(k, "key", None) in wide for k in path):
            return a
        host = np.asarray(a)
        return host.astype(ml_dtypes.float8_e4m3fn).astype(host.dtype)

    return jax.tree_util.tree_map_with_path(low, params)


def kernels_against_a_sort(seed: int, t_q: int = 2048, s_len: int = 26624,
                           k: int = 2048) -> dict:
    """The chosen sets of the kernels' path against a host sort's, on
    contrived keys at the cell's size: rows that differ, by case."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import indexed_attention as ix
    rng = np.random.default_rng(seed)
    heads, d = 16, 64

    def whole(shape, most):
        return jnp.asarray(rng.integers(-most, most + 1, shape), jnp.float32)

    def sorted_sets(scores):
        scores = np.asarray(scores)
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        want = np.zeros(scores.shape, bool)
        np.put_along_axis(want, order, True, axis=1)
        return want & np.isfinite(scores)

    keys = jnp.pad(whole((s_len, d), 3), ((0, 0), (0, 128 - d)))
    q, w = whole((t_q, heads, d), 3), whole((t_q, heads), 2)
    out = {}
    for first in (0, (s_len - t_q) // 2 // t_q * t_q, s_len - t_q):
        got = jax.jit(lambda q, w, keys: ix.topk_mask(
            ix.index_scores(q, w, keys, first), k))(q, w, keys)
        plain = jax.jit(lambda q, w, keys: ix._scores_tiles(
            q, w, keys[:, :d], first + jnp.arange(t_q)))(q, w, keys)
        differ = (np.asarray(got) != sorted_sets(plain)).any(axis=1)
        out[f"chunk_at_{first}"] = {
            "rows": t_q, "rows_differing": int(differ.sum()),
            "distinct_scores_a_row": int(np.median(
                [len(np.unique(r[np.isfinite(r)])) for r in
                 np.asarray(plain)[::256]]))}
    # a decode step's rows: the last four queries' scores, the own last
    ctx = jnp.asarray([s_len // 4, s_len // 2, s_len - 700, s_len - 1],
                      jnp.int32)
    cached = np.where(np.arange(s_len)[None] < np.asarray(ctx)[:, None],
                      np.asarray(plain)[-4:], -np.inf)
    rows = jnp.asarray(np.concatenate(
        [cached, np.asarray(plain)[-4:, :1]], axis=1), jnp.float32)
    listed, count = jax.jit(lambda r, c: ix.top_positions(r, c, k))(rows, ctx)
    want = sorted_sets(rows)
    differ = 0
    for b in range(4):
        ids = np.asarray(listed[b, :int(count[b])])
        ids = np.where(ids == int(ctx[b]), s_len, ids)    # the own: last
        differ += sorted(ids.tolist()) != np.flatnonzero(want[b]).tolist()
    out["decode_rows"] = {"rows": 4, "rows_differing": differ}
    out["device"] = jax.devices()[0].device_kind
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--kernels", action="store_true")
    args = ap.parse_args()
    out = Path("chiprun_out") / "keye_check.jsonl"
    out.parent.mkdir(exist_ok=True)
    if args.kernels:
        row = {"seed": args.seed, "kernels": kernels_against_a_sort(args.seed)}
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
        return
    from perfbench import run as runner
    seed, args.seconds, args.trace = args.seed, 0.0, 0
    _, _, ctx = runner.prepare(args)
    from perfbench.jobs import serve
    t0 = time.perf_counter()
    row = {"seed": seed}
    with broken(args.fault) if args.fault else contextlib.nullcontext():
        served = serve.Served(ctx)
        try:
            t1 = time.perf_counter()
            row["setup_s"] = t1 - t0
            row[f"fault_{args.fault}" if args.fault else "sound"] = \
                served.check_logits(seed)
            row["check_s"] = time.perf_counter() - t1
            if not args.no_control and not args.fault:
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
