"""The chunked gated delta rule alone on the chip, at the Qwen3-Next
cell's shape (2 x 8,192 positions, 16 key and 32 value heads of 128,
bf16): device time of one forward and of one forward-and-backward from a
profiler capture, for the Pallas kernels the cell runs and for the XLA
form by chunk size and by the precision of the solve, beside each
variant's distance from the token-by-token recurrence in float32.

    chiprun -- python benchmarks/delta_rule_bench.py [variant ...]
    chiprun -- python benchmarks/delta_rule_bench.py --qkv

``--qkv`` (PR 70) times the rule on a conv's output, q | k | v (2, 8192,
8192) unnormed, in the two ways a mixer can hand it over: ``whole``
(``gated_delta_rule_qkv``: the kernels read the one array and norm what
they load) beside ``apart`` (XLA's ``l2norm_heads`` and slices, then the
same kernels on three normed arrays), forward and backward with respect
to qkv, g and beta: each kernel's own ms a call with and without the norm,
what XLA does round them, and how far the two results differ.

Prints one JSON line a variant and appends them to
``chiprun_out/delta_rule_bench.jsonl``.  Fails off the chip.
"""

from __future__ import annotations

import functools
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

B, T, G, H, DK, DV = 2, 8192, 16, 32, 128, 128
# name, chunk, the solve's precision, whether the kernels may run (what
# ``gated_delta_rule`` picks at this shape) or the XLA form is called
VARIANTS = [("kernels", 64, "highest", True),
            ("chunk64_highest", 64, "highest", False),
            ("chunk64_high", 64, "high", False),
            ("chunk64_bf16", 64, "bf16", False),
            ("chunk32_highest", 32, "highest", False),
            ("chunk128_highest", 128, "highest", False)]
KERNELS = ("delta_rule_solve_bwd", "delta_rule_solve", "delta_rule_bwd",
           "delta_rule_fwd")    # a name that holds another stands before it


def ms_a_call_by_kernel(traced, calls):
    """{kernel: its device ms a call}, over the devices' events."""
    out = dict.fromkeys(KERNELS, 0.0)
    for events in traced["device"].values():
        for name, _, d in events:
            hit = next((k for k in KERNELS if k in name.lower()), None)
            if hit:
                out[hit] += d * 1e3 / calls / len(traced["device"])
    return out


def whole_and_apart(jax, jnp, np, trace, dr, out) -> None:
    """The ``--qkv`` rows."""
    keys = jax.random.split(jax.random.key(1), 4)
    # a conv's silu leaves small numbers of either sign
    qkv = jax.nn.silu(jax.random.normal(
        keys[0], (B, T, 2 * G * DK + H * DV))).astype(jnp.bfloat16)
    step = jnp.exp(jax.random.uniform(keys[1], (B, T, H), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    g = -step * jax.random.uniform(keys[2], (H,), jnp.float32, 0.0, 16.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (B, T, H)))

    def apart(qkv, g, beta):
        return dr.gated_delta_rule(*dr.apart(qkv, G, DK, H), g, beta)[0]

    def whole(qkv, g, beta):
        return dr.gated_delta_rule_qkv(qkv, g, beta, G, DK)[0]

    def loss(rule, *a):
        o = rule(*a)
        return o.astype(jnp.float32).sum(), o
    got = {}
    for name, rule in (("whole", whole), ("apart", apart)):
        both = jax.jit(jax.value_and_grad(functools.partial(loss, rule),
                                          argnums=(0, 1, 2), has_aux=True))
        (_, o), (dqkv, _, _) = both(qkv, g, beta)
        got[name] = [np.asarray(x, np.float32) for x in (o, dqkv)]
        with tempfile.TemporaryDirectory() as d:
            capture = trace.Capture(d)
            capture.start()
            for _ in range(3):
                jax.block_until_ready(both(qkv, g, beta))
            capture.stop()
            traced = trace.load_window(capture)
        kernels = ms_a_call_by_kernel(traced, 3)
        busy = trace.busy_seconds(traced) * 1e3 / 3
        row = {"variant": f"qkv_{name}", "fwd_bwd_ms": busy,
               "kernel_ms": kernels,
               "kernels_ms": sum(kernels.values()),
               "xla_ms": busy - sum(kernels.values()),
               "top_ops_ms": [[n, round(s * 1e3 / 3, 3)]
                              for n, s in trace.top_ops(traced, 12)]}
        if name == "apart":
            for what, a, b in zip(("o", "dqkv"), got["whole"], got["apart"]):
                row[f"{what}_max_abs_diff_over_max"] = float(
                    np.abs(a - b).max() / np.abs(b).max())
                row[f"{what}_share_of_entries_that_differ"] = float(
                    (a != b).mean())
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")


def main() -> None:
    import os
    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from perfbench import trace
    from perfbench.reference import qwen3_next_ref as ref
    from ray_tpu.ops import delta_rule as dr

    if jax.default_backend() != "tpu":
        raise SystemExit("delta_rule_bench measures a TPU")
    out = Path("chiprun_out") / "delta_rule_bench.jsonl"
    out.parent.mkdir(exist_ok=True)
    if sys.argv[1:] == ["--qkv"]:
        return whole_and_apart(jax, jnp, np, trace, dr, out)
    keys = jax.random.split(jax.random.key(0), 6)
    q = dr.l2norm(jax.random.normal(keys[0], (B, T, G, DK))) * DK ** -0.5
    k = dr.l2norm(jax.random.normal(keys[1], (B, T, G, DK)))
    v = jax.random.normal(keys[2], (B, T, H, DV)) * 0.5
    step = jnp.exp(jax.random.uniform(keys[3], (B, T, H), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    g = -step * jax.random.uniform(keys[4], (H,), jnp.float32, 0.0, 16.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (B, T, H)))
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([jax.jit(ref.recurrence)(
            jnp.repeat(q[i].astype(jnp.float32), H // G, 1),
            jnp.repeat(k[i].astype(jnp.float32), H // G, 1),
            v[i].astype(jnp.float32), g[i], beta[i])[0] for i in range(B)])
    want = np.asarray(want)
    mm = dr._mm
    solves = {
        "highest": mm,
        "high": lambda a, b: jnp.matmul(a, b, precision=lax.Precision.HIGH),
        "bf16": lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32),
    }
    zeros = jnp.zeros((B, H, DK, DV), jnp.float32)
    asked = sys.argv[1:]
    for name, chunk, solve, kernels in VARIANTS:
        if asked and name not in asked:
            continue
        dr._mm = solves[solve]
        jax.clear_caches()
        rule = (lambda *a: dr.gated_delta_rule(*a, chunk=chunk)) if kernels \
            else (lambda *a: dr._spans_form(*a, chunk, zeros))
        fwd = jax.jit(lambda *a: rule(*a)[0])
        both = jax.jit(jax.grad(
            lambda *a: rule(*a)[0].astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4)))
        args = (q, k, v, g, beta)
        got = np.asarray(fwd(*args), np.float32)
        jax.block_until_ready(both(*args))
        row = {"variant": name, "chunk": chunk, "solve": solve,
               "kernels": kernels,
               "max_abs_err_over_max": float(np.abs(got - want).max()
                                             / np.abs(want).max())}
        for label, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", both)):
            with tempfile.TemporaryDirectory() as d:
                capture = trace.Capture(d)
                capture.start()
                for _ in range(3):
                    jax.block_until_ready(fn(*args))
                capture.stop()
                traced = trace.load_window(capture)
            row[label] = trace.busy_seconds(traced) * 1e3 / 3
            if kernels:     # the kernels' own time (a stand-alone program
                # names them after the jitted function), and what is round them
                row[f"{label[:-3]}_kernels_ms"] = \
                    trace.op_seconds(traced, "delta_rule") * 1e3 / 3
                row[f"{label[:-3]}_top_ops_ms"] = [
                    [n, round(s * 1e3 / 3, 3)]
                    for n, s in trace.top_ops(traced, 8)]
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
    dr._mm = mm


if __name__ == "__main__":
    main()
