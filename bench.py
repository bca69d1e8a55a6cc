"""GPT-2 train-step throughput (tokens/s/chip) on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Runs the flagship GPT-2-124M single-chip train step (bf16, remat,
one-jit fwd+bwd+adamw — ray_tpu.parallel.spmd) and reports
tokens/s/chip.  Without a chip it fails: a CPU time is not a device
metric.  ``--cpu-smoke`` runs the tiny preset on the CPU instead and
prints under a CPU name only, so the output contract stays testable.
``vs_baseline`` is model-FLOPs-utilization relative to a
0.35 MFU reference point — the typical MFU of the reference framework's
torch-DDP GPT-2 runs on A100s (BASELINE.md north-star is per-chip parity
with Ray-on-A100; BASELINE.json shipped no published numbers, so the MFU
ratio is the hardware-neutral comparison).  vs_baseline > 1.0 means this
framework extracts more of its chip than the reference stack did of its.

Extra diagnostic fields are allowed by the driver contract only inside the
single JSON object; everything else goes to stderr.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time


# Peak bf16 TFLOP/s per chip by TPU generation (public spec sheets).
PEAK_TFLOPS = {"v4": 275.0, "v5e": 197.0, "v5p": 459.0, "v6e": 918.0}
A100_REFERENCE_MFU = 0.35


def _platform_peak(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    if "v5 lite" in kind or "v5e" in kind:
        return PEAK_TFLOPS["v5e"]
    if "v6" in kind:
        return PEAK_TFLOPS["v6e"]
    if "v5" in kind:
        return PEAK_TFLOPS["v5p"]
    if "v4" in kind:
        return PEAK_TFLOPS["v4"]
    raise ValueError(f"no peak FLOP/s known for device kind {kind!r}: "
                     "add it to PEAK_TFLOPS with its source")


def _delivered_matmul_tflops(jax, jnp) -> dict:
    """Delivered bf16 matmul TF/s on THIS chip, measured in-process with the
    same sync discipline as the step timing (pipelined dispatch + final
    device_get of a scalar).  Two variants so the number is reproducible
    regardless of dispatch style:

    - ``pipelined``: 30 jitted (4096,4096) bf16 matmul dispatches, one sync.
    - ``fused_pipelined``: 10 dispatches each fusing 50 matmuls in ONE
      lax.scan program, one sync — amortizes per-dispatch overhead and is
      the closest to what a train step's single big program sees.

    Both end in a device_get of a scalar, so the host clock stops only
    when the device has finished."""
    import time

    N = 4096
    flop = 2 * N**3
    key = jax.random.key(0)
    a0 = jax.random.normal(key, (N, N), jnp.bfloat16)
    w = jax.random.normal(key, (N, N), jnp.bfloat16)

    @jax.jit
    def mm(a):
        return (a @ w).astype(jnp.bfloat16)

    def body(c, _):
        return (c @ w).astype(jnp.bfloat16), ()

    @jax.jit
    def fused(a):
        c, _ = jax.lax.scan(body, a, None, length=50)
        return c

    def sync(x):
        return float(jax.device_get(jnp.sum(x[0, :4])))

    sync(mm(a0))
    sync(fused(a0))  # warm both compiles, drain queue

    t0 = time.perf_counter()
    c = a0
    for _ in range(30):
        c = mm(c)
    sync(c)
    pipelined = 30 * flop / (time.perf_counter() - t0) / 1e12

    # best-of-3: "delivered" is a CEILING measurement — a loaded-host dip
    # in a single pass would understate the chip and overstate
    # mfu_vs_delivered
    fused_pipelined = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        c = a0
        for _ in range(10):
            c = fused(c)
        sync(c)
        fused_pipelined = max(
            fused_pipelined,
            500 * flop / (time.perf_counter() - t0) / 1e12)
    return {"pipelined": round(pipelined, 1),
            "fused_pipelined": round(fused_pipelined, 1)}


# Device-trace op classes for the overlap breakdown.  Fusion names in
# XLA device traces carry the HLO op of their root: collectives are
# all-reduce/all-gather/reduce-scatter/collective-permute (+ the jax
# spellings psum/ppermute); everything else on a compute lane counts as
# compute.  Ordered: first substring hit names the op KIND so exposed
# time is attributable per collective family, not just visible in
# aggregate ("collective-permute" before "permute"-free fallbacks;
# "reduce-scatter" before "all-reduce" would also match "reduce").
_COLLECTIVE_KINDS = (
    ("reduce-scatter", "reduce_scatter"),
    ("all-reduce", "psum"),
    ("psum", "psum"),
    ("all-gather", "all_gather"),
    ("collective-permute", "ppermute"),
    ("ppermute", "ppermute"),
    ("all-to-all", "all_to_all"),
)
_COLLECTIVE_PAT = tuple(p for p, _ in _COLLECTIVE_KINDS)


def _collective_kind(name: str):
    for pat, kind in _COLLECTIVE_KINDS:
        if pat in name:
            return kind
    return None


def _merged_busy_us(intervals) -> float:
    """Total busy time of a set of (ts, dur) device events, overlaps
    merged — the union length, not the sum."""
    if not intervals:
        return 0.0
    ivs = sorted((ts, ts + dur) for ts, dur in intervals)
    total = 0.0
    cur_s, cur_e = ivs[0]
    for s, e in ivs[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s)


def _overlap_breakdown(jax, step_once, steps: int = 3):
    """Collective-vs-compute span accounting per train step (the ROADMAP
    item-4 prerequisite): run ``steps`` steps under a jax device trace,
    bucket device events into collective vs compute, and report per-step
    busy time, the overlapped fraction, and the EXPOSED collective time
    (collective busy that no compute hides) — the number the
    overlap-scheduled step must drive to zero.

    Only DEVICE-lane events count: jax's profiler writes host threads
    (python / TSL TraceMe spans) into the same trace files, and a host
    span covering the whole step would land in "compute" and make every
    collective look hidden.  Lanes are identified by their
    ``process_name`` metadata containing ``/device:``.  The CPU backend
    has none and gets None; on a chip a trace without device lanes is a
    failed measurement and raises."""
    import shutil
    import tempfile

    from ray_tpu.util.tracing import profile_event_lists

    out_dir = tempfile.mkdtemp(prefix="rtpu_overlap_")
    try:
        with jax.profiler.trace(out_dir):
            for _ in range(steps):
                step_once()
        coll, comp = [], []
        by_kind: dict = {}
        for raw in profile_event_lists(out_dir):
            dev_pids = {
                e.get("pid") for e in raw
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "/device:" in str((e.get("args") or {}).get("name", ""))}
            for e in raw:
                if e.get("ph") != "X" or e.get("ts") is None \
                        or e.get("pid") not in dev_pids:
                    continue
                name = str(e.get("name", "")).lower()
                dur = float(e.get("dur", 0) or 0)
                if not dur:
                    continue
                iv = (float(e["ts"]), dur)
                kind = _collective_kind(name)
                if kind is not None:
                    coll.append(iv)
                    by_kind.setdefault(kind, []).append(iv)
                else:
                    comp.append(iv)
        if not coll and not comp:
            platform = jax.devices()[0].platform
            if platform != "cpu":
                raise RuntimeError(
                    f"the profiler trace of {steps} steps on {platform!r} "
                    "has no device lanes: no overlap breakdown")
            return None
        coll_us = _merged_busy_us(coll)
        comp_us = _merged_busy_us(comp)
        both_us = _merged_busy_us(coll + comp)
        overlapped_us = max(0.0, coll_us + comp_us - both_us)
        exposed_us = coll_us - overlapped_us

        # Per-kind exposed time: the kind's busy minus its overlap with
        # COMPUTE (not with other collectives — two collectives hiding
        # behind each other are both still exposed).  Regressions become
        # attributable to the op family that regressed, not just visible
        # in the aggregate.
        def _exposed(kind_ivs):
            k_us = _merged_busy_us(kind_ivs)
            hidden = max(0.0, k_us + comp_us
                         - _merged_busy_us(kind_ivs + comp))
            return k_us - hidden

        exposed_by_kind = {
            k: round(_exposed(ivs) / steps / 1e3, 3)
            for k, ivs in sorted(by_kind.items())}
        return {
            "steps": steps,
            "compute_ms_per_step": round(comp_us / steps / 1e3, 3),
            "collective_ms_per_step": round(coll_us / steps / 1e3, 3),
            "exposed_collective_ms_per_step":
                round(exposed_us / steps / 1e3, 3),
            "exposed_ms_by_kind_per_step": exposed_by_kind,
            "overlap_frac":
                round(overlapped_us / coll_us, 4) if coll_us else None,
        }
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny preset on the CPU, printed under a CPU "
                         "name: keeps the output contract testable and "
                         "measures nothing")
    args = ap.parse_args()

    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib, spmd
    from ray_tpu.parallel.mesh import MeshConfig

    dev = jax.devices()[0]
    on_tpu = not args.cpu_smoke
    if on_tpu and dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU chip and jax found {dev.platform!r}; "
            "a CPU time is not a device metric (--cpu-smoke exercises the "
            "output contract only)")
    if args.cpu_smoke and dev.platform != "cpu":
        raise SystemExit("--cpu-smoke runs on the CPU: set JAX_PLATFORMS=cpu")
    if on_tpu:
        import dataclasses
        # flash (Pallas, block=512 via pick_block_size) beats XLA dense by
        # ~35% at this config on v5e — and is now the model DEFAULT on
        # TPU (attn_impl="auto"), not a bench-only override.
        # remat_policy="attn_qkv" pins the flash out/lse residuals + the
        # qkv projection across the remat boundary — the backward re-runs
        # neither the attention kernel nor the qkv matmul (r3/r4
        # device-trace work; benchmarks/results/step_breakdown_r04.md).
        cfg = dataclasses.replace(gpt2.gpt2_small(),
                                  remat_policy="attn_qkv")
        batch, seq, steps = 32, 1024, 20
    else:
        cfg = gpt2.tiny(vocab=512, seq=128)
        batch, seq, steps = 8, 64, 3

    import jax.numpy as _jnp
    mc = MeshConfig(data=1).resolved(1)
    mesh = mesh_lib.build_mesh(mc, [dev])
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        # bf16 moment storage (r4, parallel/optim.py): halves the
        # bandwidth-floored AdamW phase's state traffic
        optimizer=spmd.default_optimizer(
            moments_dtype=_jnp.bfloat16 if on_tpu else None),
        mesh=mesh, mesh_config=mc)
    state = prog.init_fn(jax.random.key(0))

    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    b = spmd.shard_batch(prog, {"inputs": toks[:, :-1],
                                "targets": toks[:, 1:]})

    # warmup / compile; every sync is a device_get of a scalar
    t0 = time.perf_counter()
    state, m = prog.step_fn(state, b)
    float(jax.device_get(m["loss"]))
    compile_s = time.perf_counter() - t0
    state, m = prog.step_fn(state, b)
    float(jax.device_get(m["loss"]))

    # Pipelined dispatch (async queue) + one final sync: measures device
    # throughput, not the host's dispatch latency.
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = prog.step_fn(state, b)
    float(jax.device_get(m["loss"]))
    step_s = (time.perf_counter() - t0) / steps

    # Overlap breakdown (ROADMAP item 4 prerequisite): where does the
    # step's device time go — compute, collectives, and how much of the
    # collective time is EXPOSED (unhidden by compute)?
    _ostate = [state]

    def _step_once():
        _ostate[0], mm = prog.step_fn(_ostate[0], b)
        float(jax.device_get(mm["loss"]))
    overlap = _overlap_breakdown(jax, _step_once,
                                 steps=3 if on_tpu else 2)

    tokens_per_step = batch * seq
    tok_s = tokens_per_step / step_s
    run = {
        "value": round(tok_s, 1),
        "step_ms": round(step_s * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "device": getattr(dev, "device_kind", dev.platform),
        "batch": batch, "seq": seq,
        "loss": round(float(jax.device_get(m["loss"])), 4),
        "overlap_breakdown": overlap,
    }
    if not on_tpu:
        print(json.dumps({"metric": "gpt2_tiny_cpu_smoke_tokens_per_s",
                          "unit": "tokens/s on the CPU (no device metric)",
                          **run}))
        return
    fpt = gpt2.flops_per_token(cfg, seq)
    peak = _platform_peak(dev) * 1e12
    mfu = tok_s * fpt / peak
    # In-bench calibration (VERDICT r1 weak #2): delivered matmul rate
    # measured in this same process with this same sync discipline, so the
    # MFU claim is reproducible without trusting spec-sheet peak.
    import jax.numpy as jnp
    delivered = _delivered_matmul_tflops(jax, jnp)
    delivered_peak = max(delivered["pipelined"],
                         delivered["fused_pipelined"]) * 1e12
    out = {
        "metric": "gpt2_124m_train_tokens_per_s_per_chip",
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / A100_REFERENCE_MFU, 4),
        "mfu": round(mfu, 4),
        **run,
        "delivered_matmul_tflops": delivered,
        "model_tflops": round(tok_s * fpt / 1e12, 1),
        "mfu_vs_delivered": round(tok_s * fpt / delivered_peak, 4),
    }
    # The BASELINE #5 flagship at its NAMED size: GPT-2-XL 1.5B,
    # single-chip fit via bf16 master params + bf16 Adam moments +
    # remat "attn" (r4).
    del state, prog, b
    out["xl_1558m"] = _run_xl(jax, np, gpt2, mesh_lib, spmd, MeshConfig,
                              dev, peak)
    print(json.dumps(out))


def _run_xl(jax, np, gpt2, mesh_lib, spmd, MeshConfig, dev,
            peak: float) -> dict:
    import dataclasses
    import jax.numpy as jnp
    cfg = dataclasses.replace(gpt2.gpt2_xl(), remat_policy="attn",
                              param_dtype=jnp.bfloat16)
    batch, seq, steps = 8, 1024, 8
    mc = MeshConfig(data=1).resolved(1)
    mesh = mesh_lib.build_mesh(mc, [dev])
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        optimizer=spmd.default_optimizer(moments_dtype=jnp.bfloat16),
        mesh=mesh, mesh_config=mc)
    state = prog.init_fn(jax.random.key(0))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    b = spmd.shard_batch(prog, {"inputs": toks[:, :-1],
                                "targets": toks[:, 1:]})
    t0 = time.perf_counter()
    state, m = prog.step_fn(state, b)
    float(jax.device_get(m["loss"]))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = prog.step_fn(state, b)
    loss = float(jax.device_get(m["loss"]))
    step_s = (time.perf_counter() - t0) / steps
    tok_s = batch * seq / step_s
    fpt = gpt2.flops_per_token(cfg, seq)
    return {"tokens_per_s_per_chip": round(tok_s, 1),
            "mfu": round(tok_s * fpt / peak, 4),
            "vs_baseline": round(tok_s * fpt / peak / A100_REFERENCE_MFU, 4),
            "step_ms": round(step_s * 1e3, 2),
            "compile_s": round(compile_s, 1),
            "batch": batch, "loss": round(loss, 4)}


if __name__ == "__main__":
    sys.exit(main())
