"""The training job: one jitted train step, dispatched back to back.

The program under test is ``spmd.build_train_program`` over the family's
``loss_fn`` / ``init_params``, built as bench.py builds it.  Steps are
queued ahead of the device (the host waits for the loss of the step
``sync_lag`` steps back, never for the newest), so the device never waits
for the host, and the window ends on a sync: tokens of the window over
the seconds from the first dispatch to the last ``device_get``.
"""

from __future__ import annotations

import math
import time
from collections import deque

import numpy as np

from perfbench import manifest, trace, traffic

# The program computes the loss with bf16 activations and the flash
# kernel; the reference is float32 at "highest" precision.  bf16 keeps 8
# bits of mantissa, so single logits differ by up to some hundredths, but
# the loss is a mean over 2,048 tokens near ln(50257) = 10.8 and independent
# roundings average out: 7.5e-4 was the largest difference in 43 runs of the
# four cells on the v5e (PERF.md, PR 24).  0.004 is five times that, and far
# below what a wrong mask, a dropped bias, a lost shard or 8-bit arithmetic
# does to the loss (>= 0.05 at random weights).
LOSS_ATOL = 0.004


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import mesh as mesh_lib, spmd
    from ray_tpu.parallel.mesh import MeshConfig

    config, spec, devices = ctx["config_file"], ctx["traffic_file"], ctx["devices"]
    fam = manifest.family(config["family"])
    mod = fam.module()
    options = config["train"]
    model_cfg = fam.model_config(config, options["model_options"])

    mc = MeshConfig(**options["mesh"]).resolved(len(devices))
    mesh = mesh_lib.build_mesh(mc, devices)
    moments = options["optimizer"].get("moments_dtype")
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: mod.loss_fn(p, b, model_cfg),
        init_params_fn=lambda rng: mod.init_params(rng, model_cfg),
        optimizer=spmd.default_optimizer(
            moments_dtype=jnp.dtype(moments) if moments else None),
        mesh=mesh, mesh_config=mc)
    state = prog.init_fn(jax.random.key(traffic.key_seed(ctx["seed"])))
    jax.block_until_ready(state.step)
    ctx["marks"]["state_s"] = time.perf_counter() - ctx["t_start"]

    tokens = traffic.train_batches(spec, config["vocab_size"], ctx["seed"])
    ring = [spmd.shard_batch(prog, {"inputs": t[:, :-1], "targets": t[:, 1:]})
            for t in tokens]

    def sync(loss) -> float:
        with trace.span("pb.sync"):
            return float(jax.device_get(loss))

    def dispatch(state, i):
        with trace.span("pb.dispatch"):
            state, metrics = prog.step_fn(state, ring[i % len(ring)])
        return state, metrics["loss"]

    # warm-up: the first call compiles or loads the step, the second
    # shows that it runs again on donated buffers
    warm_losses = []
    for i in range(spec["warmup_steps"]):
        state, loss = dispatch(state, i)
        warm_losses.append(sync(loss))
        ctx["marks"][f"warm_step_{i}_s"] = time.perf_counter() - ctx["t_start"]
    setup_s = time.perf_counter() - ctx["t_start"]

    # ------------------------------------------------------------ window
    lag, min_steps = spec["sync_lag"], spec["min_steps"]
    capture = trace.Capture(ctx["trace_dir"]) if ctx["trace"] else None
    trace_from = ctx["seconds"] - min(spec["trace_seconds"], ctx["seconds"])
    pending: deque = deque()
    losses = []
    steps = 0
    t0 = time.perf_counter()
    while True:
        if capture and not capture.started and \
                time.perf_counter() - t0 >= trace_from:
            capture.start()
        state, loss = dispatch(state, spec["warmup_steps"] + steps)
        steps += 1
        pending.append(loss)
        if len(pending) > lag:
            losses.append(sync(pending.popleft()))
            if time.perf_counter() - t0 >= ctx["seconds"] \
                    and steps >= min_steps:
                break
    while pending:
        losses.append(sync(pending.popleft()))
    window_s = time.perf_counter() - t0
    traced = None
    if capture:
        capture.stop()
        traced = trace.load_window(capture)

    # ---------------------------------------------------- outside the window
    k = len(ring)
    finite = all(math.isfinite(x) for x in warm_losses + losses)
    falling = sum(losses[-k:]) / k < sum(losses[:k]) / k
    n_check = spec["check_sequences"]
    sample = {"inputs": tokens[0][:n_check, :-1],
              "targets": tokens[0][:n_check, 1:]}
    params = state.params
    del state

    def program_loss(p, b):
        with mesh_lib.ambient_mesh(mesh):
            return mod.loss_fn(p, b, model_cfg)

    got = float(jax.device_get(
        jax.jit(program_loss)(params, spmd.shard_batch(prog, sample))))
    want = float(np.mean(jax.device_get(fam.reference_loss(
        params, sample["inputs"], sample["targets"], config))))
    loss_diff = abs(got - want)
    checks = {"losses_finite": finite, "losses_falling": falling,
              "loss_vs_reference": loss_diff <= LOSS_ATOL}

    peak = None if ctx["rehearse"] else ctx["peaks"]["bf16_flops_per_s"]
    return {
        "setup_s": setup_s, "window_s": window_s, "steps": steps,
        "tokens": steps * spec["batch"] * spec["seq"],
        "chips": len(devices),
        "flops_per_token": fam.flops_per_token(config, spec["seq"]),
        "peak_flops_per_s": peak,
        "attempted": steps, "failed": 0 if finite else steps,
        "correct": all(checks.values()), "checks": checks,
        "compared": {"loss_abs_diff": [loss_diff, LOSS_ATOL]},
        "notes": {"first_losses": losses[:k], "last_losses": losses[-k:],
                  "program_loss": got, "reference_loss": want,
                  "loss_abs_diff": loss_diff, "loss_atol": LOSS_ATOL},
        "trace": traced,
    }
