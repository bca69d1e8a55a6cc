"""The router's choice of k experts alone on the chip: ``lax.top_k`` (and,
where a bias decides, ``take_along_axis``) with the scatter-add that is
their own derivative, beside ``ops/moe.choose_experts`` (the Pallas kernel
``router_choice`` where the rule sends a shape to it, a select over (N, E)
as the backward everywhere), at the three routed training cells' shapes
and at a 2,048-row prefill chunk of 128 experts.

    chiprun -- python benchmarks/router_choice_bench.py

Device time of one forward and of one forward-and-backward from a profiler
capture, the kernel's own time in it, the largest operations, and whether
the two forms' ids, picked numbers and gradients are the same bits.
Prints one JSON line a shape and form and appends them to
``chiprun_out/router_choice_bench.jsonl``.  Fails off the chip.

Measured on the chip (TPU v5 lite, 2026-10-04, PR 68), ms a call, the
kernel's own time in brackets:

                        lax.top_k          choose_experts
                        fwd    fwd+bwd     fwd            fwd+bwd
    (16384, 512, 10)    1.443  3.061       0.138 [0.134]  0.256
    (16384, 128,  6)    1.052  1.940       0.045 [0.043]  0.126
    ( 8192,  64,  8)    0.044  0.488       0.044 [none]   0.049
    ( 2048, 128,  8)    0.030  0.176       0.007 [0.007]  0.019

ids, picked numbers and gradients the same bits at all four.  The layouts
were timed first, each alone (the kernel and, in brackets, the call with
XLA's transpose of the keys where a layout needs one), at the first two
shapes: experts along the lanes 0.183 and 0.122; along the sublanes with
the (N, E) keys turned by XLA 0.125 [0.186] and 0.030 [0.062]; turned in
VMEM 0.145 and 0.044 with int32 ids, 0.134 and 0.043 with float32 ids, the
form kept.  Tiles of 512 tokens lie within 0.01 ms of 256 along the
sublanes and are four times slower along the lanes.
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# rows, experts, k, whether a bias decides (``route_sigmoid``): Qwen3-Next's
# step, Kanana's, OLMoE's (64 experts: ``lax.top_k`` stays, the backward is
# the select), a 2,048-position chunk of SDAR's or Keye's prompts
SHAPES = {
    "qwen3_next_step": (16384, 512, 10, False),
    "kanana_step": (16384, 128, 6, True),
    "olmoe_step": (8192, 64, 8, False),
    "prefill_chunk": (2048, 128, 8, False),
}
RUNS = 5


def main() -> None:
    import os
    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from perfbench import trace
    from ray_tpu.ops import moe

    if jax.default_backend() != "tpu":
        raise SystemExit("router_choice_bench measures a TPU")

    def top_k(keys, payload, k):
        vals, idx = lax.top_k(keys, k)
        return idx, vals if payload is None else jnp.take_along_axis(
            payload, idx, axis=-1)

    def captured(fn, *args):
        jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory() as d:
            capture = trace.Capture(d)
            capture.start()
            for _ in range(RUNS):
                jax.block_until_ready(fn(*args))
            capture.stop()
            traced = trace.load_window(capture)
        return {"ms": trace.busy_seconds(traced) * 1e3 / RUNS,
                "router_choice_ms":
                    trace.op_seconds(traced, "router_choice") * 1e3 / RUNS,
                "top_ops_ms": [[n, round(s * 1e3 / RUNS, 4)]
                               for n, s in trace.top_ops(traced, 4)]}

    def measured(name, form, choose):
        """One shape under one form: the line, and what it computed."""
        n, e, k, biased = SHAPES[name]
        a, b, c = jax.random.split(jax.random.key(0), 3)
        logits = 2 * jax.random.normal(a, (n, e), jnp.float32)
        if biased:
            scores = jax.nn.sigmoid(logits)
            operands = (scores + 0.1 * jax.random.normal(b, (e,)), scores)
        else:
            operands = (jax.nn.softmax(logits, -1),)
        probe = jax.random.normal(c, (n, k), jnp.float32)

        def both(keys, payload=None):
            return choose(keys, payload, k)

        def loss(*xs):
            return (both(*xs)[1] * probe).sum()
        fwd = jax.jit(both)
        # the payload alone is differentiated: the keys decide
        grad = jax.jit(jax.value_and_grad(loss, argnums=len(operands) - 1))
        row = {"shape": name, "n": n, "experts": e, "k": k, "form": form,
               "in_kernel": choose is moe.choose_experts
               and moe._choice_in_kernel(n, e)}
        for label, fn in (("fwd", fwd), ("fwd_bwd", grad)):
            row.update({f"{label}_{key}": value
                        for key, value in captured(fn, *operands).items()})
        return row, [np.asarray(x) for x in
                     (*fwd(*operands), grad(*operands)[1])]

    out = Path("chiprun_out") / "router_choice_bench.jsonl"
    out.parent.mkdir(exist_ok=True)
    for name in SHAPES:
        parent, want = measured(name, "lax.top_k", top_k)
        change, got = measured(name, "choose_experts", moe.choose_experts)
        for what, x, y in zip(("ids", "picked", "gradient"), got, want):
            change[f"{what}_equal"] = bool(np.array_equal(x, y))
        for row in (parent, change):
            print(json.dumps(row), flush=True)
            with out.open("a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
