"""Chip smoke: GPT-2-124M trains and answers requests on one TPU chip.

    python chip_smoke.py              # one chip: phases native, train,
                                      #   framework, serve
    python chip_smoke.py --chips 4    # four chips: the mesh phase only
    python chip_smoke.py --rehearse   # tiny size on the CPU; never a pass

One process per chip: this parent never imports jax (nor anything that
does), every phase is a child process of its own, run one after the
other, and a failing child ends the script at once with its exit code.
Every child prints the platform, device kind, device count and its compile
seconds, and fails when the platform is not ``tpu``.  The last line of
stdout is the result, ``{"ok": true, "device": {...}}``; a rehearsal
prints ``"ok": false`` and the ``cpu`` platform there instead.

What the phases prove is in their docstrings.  Results and, on failure,
the worker logs are kept under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
RUN_DIR = HERE / "chiprun_out" / "chip_smoke"
SEED = 0
TOTAL_BUDGET_S = 1150.0            # the contract allows 1200
PHASE_TIMEOUT_S = {"native": 180, "train": 420, "framework": 420,
                   "serve": 420, "mesh4": 900}
# Phase B repeats Phase A's program from the same seed on the same chip,
# so its losses are the same numbers; the slack is for a compiler that
# is not bit-reproducible from one process to the next.
SAME_PROGRAM_RTOL = 1e-5
# Serving prefill (flash kernel) against a plain dense forward, both with
# bf16 activations: bf16 keeps 8 mantissa bits (2**-8 = 0.004 relative)
# and the logits are O(1) after 12 layers, so elementwise agreement to a
# few hundredths is what equal arithmetic gives; a wrong kernel is off by
# the logits' own spread (~0.5 at random weights).
PREFILL_LOGIT_ATOL = 0.08
# Four chips against one: the same bf16 arithmetic, but every contraction
# over a sharded dimension sums partial products in another order.  The
# loss is a mean over 32,768 tokens near ln(50257) = 10.8, so independent
# roundings average out (5.7e-6 apart on the v5e, PR 22); 1e-3 leaves
# room for a compiler that orders sums otherwise, and is far below what
# a doubled bias or a lost shard does to the loss.
MESH4_LOSS_ATOL = 1e-3


# ------------------------------------------------------------------ children
def _say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _device_report(rehearse: bool) -> dict:
    """Print what JAX runs on; anything but a TPU fails a real run."""
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    _say("platform", info["platform"])
    _say("device_kind", info["kind"])
    _say("device_count", info["count"])
    _say("compile_cache_dir", jax.config.jax_compilation_cache_dir)
    if info["platform"] != "tpu" and not rehearse:
        raise SystemExit(f"FAIL: platform is {info['platform']!r}, not 'tpu'")
    return info


def _count_cache_events() -> dict:
    """Live counts of this process's persistent-compile-cache traffic
    (programs looked up, found, written), which tell a cold run from a
    warm one better than seconds do."""
    from jax import monitoring
    names = {"/jax/compilation_cache/compile_requests_use_cache": "looked_up",
             "/jax/compilation_cache/cache_hits": "found",
             "/jax/compilation_cache/cache_misses": "written"}
    counts = dict.fromkeys(names.values(), 0)

    def on_event(event: str, **_) -> None:
        if event in names:
            counts[names[event]] += 1

    monitoring.register_event_listener(on_event)
    return counts


def _chip_fds() -> list:
    """Device nodes this process holds open (the chip, when it has it)."""
    held = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith("/dev/") and not target.startswith(
                ("/dev/null", "/dev/pts", "/dev/shm", "/dev/tty",
                 "/dev/urandom", "/dev/random", "/dev/zero")):
            held.append(target)
    return sorted(set(held))


def _use_default_compile_cache() -> None:
    """Before jax is imported: the repo's cache directory unless
    JAX_COMPILATION_CACHE_DIR names one (what bench.py and init() do)."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)


def _build_program(rehearse: bool, devices, mesh_config):
    """bench.py's program: GPT-2-124M, remat "attn_qkv", attention left at
    "auto", bf16 Adam moments, a seeded batch of 32 x 1024."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib, spmd

    if rehearse:
        # the same paths at toy size; the flash kernel is named because
        # "auto" is dense off the chip, and the vocabulary is odd because
        # 50,257 is
        cfg = gpt2.GPT2Config(vocab_size=509, n_positions=128, n_embd=128,
                              n_layer=2, n_head=2, attn_impl="flash",
                              remat_policy="attn_qkv")
        batch, seq = 4, 128
    else:
        cfg = dataclasses.replace(gpt2.gpt2_small(), remat_policy="attn_qkv")
        batch, seq = 32, 1024
    mc = mesh_config.resolved(len(devices))
    mesh = mesh_lib.build_mesh(mc, devices)
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: gpt2.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: gpt2.init_params(rng, cfg),
        optimizer=spmd.default_optimizer(moments_dtype=jnp.bfloat16),
        mesh=mesh, mesh_config=mc)
    state = prog.init_fn(jax.random.key(SEED))
    toks = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    batch_arrays = spmd.shard_batch(
        prog, {"inputs": toks[:, :-1], "targets": toks[:, 1:]})
    return cfg, prog, state, batch_arrays


def _run_steps(prog, state, batch_arrays, n: int):
    """n steps on one batch -> (state, losses, seconds of the first)."""
    import jax
    losses, compile_s = [], 0.0
    t0 = time.perf_counter()
    for i in range(n):
        state, metrics = prog.step_fn(state, batch_arrays)
        losses.append(float(jax.device_get(metrics["loss"])))
        if i == 0:
            compile_s = time.perf_counter() - t0
    return state, losses, compile_s


def _check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        raise SystemExit(f"FAIL: {what}")


def _finite(xs) -> bool:
    import math
    return all(math.isfinite(x) for x in xs)


def phase_native(args) -> dict:
    """The native store and wire codec build from ray_tpu/native/src/ with
    this machine's g++/gcc (the parent removed _build/ first); the
    framework's silent pure-Python path is a failure here."""
    from ray_tpu import native
    for tool in ("g++", "gcc"):
        _check(shutil.which(tool) is not None, f"{tool} is on PATH")
    _check(native.load_slab_lib() is not None,
           "slab_store.cc compiled and loaded")
    _check(native.load_wirecodec() is not None,
           "wirecodec.c compiled and loaded")
    built = sorted(p.name for p in (HERE / "ray_tpu/native/_build").iterdir())
    _say("native_build", built)
    return {"built": built}


def phase_train(args) -> dict:
    """Phase A: the trainer in one process.  1 compile step + 5 steps of
    bench.py's program; the attention must resolve to the flash kernel,
    the Pallas kernels must be in the step as custom calls, every loss
    finite, the last below the first, and the device's peak bytes real."""
    _use_default_compile_cache()
    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig

    cache = _count_cache_events()
    info = _device_report(args.rehearse)
    dev = jax.devices()[0]
    cfg, prog, state, b = _build_program(args.rehearse, [dev],
                                         MeshConfig(data=1))
    from ray_tpu.ops.attention import flash_runs
    runs = flash_runs(b["inputs"].shape[1], cfg.attn_impl)
    _say("attn_impl", f"{cfg.attn_impl}: " + ("flash" if runs else "dense"))
    _check(runs, "the step's attention is the flash kernel")
    lowered = prog.jitted_step.lower(state, b).as_text()
    n_custom = lowered.count("tpu_custom_call")
    _say("tpu_custom_calls_in_step", n_custom)
    if not args.rehearse:      # interpret mode has no custom call to find
        _check(n_custom > 0, "Pallas kernels are in the step as custom calls")
    state, losses, compile_s = _run_steps(prog, state, b, 6)
    _say("compile_s", round(compile_s, 2))
    _say("compile_cache", cache)
    _say("losses", losses)
    _check(_finite(losses), "every loss is finite")
    _check(losses[-1] < losses[0],
           f"loss fell on the same batch ({losses[0]:.4f} -> "
           f"{losses[-1]:.4f})")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    _say("peak_bytes_in_use", peak)
    _say("bytes_in_use", stats.get("bytes_in_use"))
    _say("bytes_limit", stats.get("bytes_limit"))
    if not args.rehearse:      # the CPU backend reports no memory stats
        _check(peak > 0, "device reports non-zero peak bytes")
    return {"device": info, "compile_s": compile_s, "losses": losses,
            "peak_bytes_in_use": peak, "tpu_custom_calls": n_custom}


def _train_loop(config: dict) -> None:
    """Runs in the trainer's TPU worker: Phase A's program for 3 steps."""
    import jax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig

    if config["rehearse"]:     # a TPU worker's environment names no platform
        jax.config.update("jax_platforms", "cpu")
    cache = _count_cache_events()
    dev = jax.devices()[0]
    _, prog, state, b = _build_program(config["rehearse"], [dev],
                                       MeshConfig(data=1))
    _, losses, compile_s = _run_steps(prog, state, b, 3)
    for loss in losses:
        train.report({
            "loss": loss, "compile_s": compile_s,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()), "pid": os.getpid(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "compile_cache": cache, "chip_fds": _chip_fds()})


def phase_framework(args) -> dict:
    """Phase B: ray_tpu.init -> a CPU task -> JaxTrainer on a TPU worker.
    This driver must never start a JAX backend: the chip is the worker's.
    The worker's losses are Phase A's first three."""
    import faulthandler

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # a worker that cannot open the chip is silent (its stdout/stderr are
    # /dev/null) and fit() would wait for ever: leave with the stacks
    faulthandler.dump_traceback_later(
        PHASE_TIMEOUT_S["framework"] - 30, exit=True)
    ctx = ray_tpu.init(num_cpus=2)
    (RUN_DIR / "framework.session").write_text(ctx["session_dir"])
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0.0)
        _say("detected_tpus", tpus)
        _check(tpus == 1.0, "init() detected one TPU chip without being told")

        @ray_tpu.remote
        def cpu_probe():
            import jax
            return {"pid": os.getpid(),
                    "platform": jax.devices()[0].platform,
                    "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
                    "chip_fds": _chip_fds()}

        cpu = ray_tpu.get(cpu_probe.remote(), timeout=120)
        _say("cpu_task", cpu)
        result = JaxTrainer(
            _train_loop, train_loop_config={"rehearse": args.rehearse},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(storage_path=str(RUN_DIR / "train_results")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    faulthandler.cancel_dump_traceback_later()
    if result.error is not None:
        raise result.error
    steps = result.metrics_history
    worker = steps[0]
    _say("platform", worker["platform"])
    _say("device_kind", worker["device_kind"])
    _say("device_count", worker["device_count"])
    _say("compile_cache_dir", worker["compile_cache_dir"])
    _say("compile_s", round(worker["compile_s"], 2))
    _say("compile_cache", worker["compile_cache"])
    _say("worker_pid", worker["pid"])
    _say("driver_pid", os.getpid())
    _say("worker_chip_fds", worker["chip_fds"])
    losses = [s["loss"] for s in steps]
    _say("losses", losses)

    _check(len(steps) == 3, "the trainer reported 3 steps")
    _check(worker["pid"] not in (os.getpid(), cpu["pid"]),
           "the trainer ran in a process of its own")
    want = "cpu" if args.rehearse else "tpu"
    _check(worker["platform"] == want, f"the TPU worker saw {want!r}")
    _check(cpu["platform"] == "cpu" and cpu["JAX_PLATFORMS"] == "cpu",
           "the CPU task saw 'cpu'")
    _check(not set(cpu["chip_fds"]) & set(worker["chip_fds"]),
           "the CPU task's process never opened the chip")
    jax_mod = sys.modules.get("jax")
    started = (jax_mod is not None
               and jax_mod._src.xla_bridge.backends_are_initialized())
    _check(not started, "the driver never initialised a JAX backend")
    _check(not set(_chip_fds()) & set(worker["chip_fds"]),
           "the driver never opened the chip")
    ref = json.loads((RUN_DIR / "train.json").read_text())
    _say("phase_a_losses", ref["losses"][:3])
    _say("phase_a_compile_s", round(ref["compile_s"], 2))
    close = all(abs(a - b) <= SAME_PROGRAM_RTOL * abs(a)
                for a, b in zip(ref["losses"], losses))
    _check(close, f"losses equal Phase A's to rtol {SAME_PROGRAM_RTOL}")
    if not args.rehearse:      # tiny programs straddle the cache's 1 s floor
        _check(worker["compile_cache"]["written"] == 0,
               "the worker found Phase A's programs in the compile cache")
    device = {"platform": worker["platform"], "kind": worker["device_kind"],
              "count": worker["device_count"]}
    return {"device": device, "compile_s": worker["compile_s"],
            "losses": losses, "worker_pid": worker["pid"]}


def phase_serve(args) -> dict:
    """Phase C: serve.llm.LLMEngine in this process.  4 seeded prompts of
    64 tokens, 16 greedy tokens each; the prefill's last-position logits
    agree with a plain dense gpt2.forward on the same params."""
    _use_default_compile_cache()
    import dataclasses
    import logging

    import jax
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.ops.attention import flash_runs
    from ray_tpu.serve import llm

    cache = _count_cache_events()
    info = _device_report(args.rehearse)

    # the engine loop logs a failed step and carries on
    failed_steps: list = []

    class _StepFailures(logging.Handler):
        def emit(self, record):
            if "engine step failed" in record.getMessage():
                failed_steps.append(record.getMessage())

    logging.getLogger("ray_tpu.serve.llm.engine").addHandler(
        _StepFailures(level=logging.ERROR))

    n_prompt, n_new = (16, 4) if args.rehearse else (64, 16)
    ecfg = llm.EngineConfig(
        model="gpt2:tiny" if args.rehearse else "gpt2:gpt2-124m",
        seed=SEED, num_blocks=64, max_model_len=128)
    t0 = time.perf_counter()
    eng = llm.LLMEngine(ecfg)
    try:
        mcfg = eng.runner.mcfg
        for t in ecfg.prefill_len_buckets:
            _say(f"prefill_bucket_{t}",
                 "flash" if flash_runs(t, mcfg.attn_impl) else "dense")
        prompts = np.random.default_rng(SEED).integers(
            0, mcfg.vocab_size, (4, n_prompt)).tolist()
        streams = [eng.submit(p, llm.SamplingParams(max_tokens=n_new))
                   for p in prompts]
        outs = [[] for _ in streams]
        done = [False] * len(streams)
        deadline = time.monotonic() + 300
        while not all(done):
            if time.monotonic() > deadline:     # a failed step never ends
                _check(False, f"every request finished (have {outs})")
            for i, s in enumerate(streams):
                if not done[i]:
                    toks, done[i] = s.poll(timeout=0.2)
                    outs[i].extend(toks)
        _say("compile_s", round(time.perf_counter() - t0, 2))
        _say("compile_cache", cache)
        _say("generated", outs)
        _check(all(len(o) == n_new for o in outs),
               f"every request produced {n_new} tokens")
        _check(all(0 <= t < mcfg.vocab_size for o in outs for t in o),
               "every token id is below the vocabulary size")

        dense = dataclasses.replace(mcfg, attn_impl="dense")
        ref_fwd = jax.jit(lambda p, t: gpt2.forward(p, t, dense)[0, -1])
        worst = 0.0
        for prompt, out in zip(prompts, outs):
            got, _, _ = eng.runner.prefill(prompt)
            ref = np.asarray(ref_fwd(eng.runner.params,
                                     np.asarray([prompt], np.int32)))
            _check(got.shape == ref.shape == (mcfg.vocab_size,)
                   and bool(np.isfinite(got).all()),
                   "prefill logits are finite and one per vocabulary entry")
            worst = max(worst, float(np.abs(got - ref).max()))
            top2 = np.sort(ref)[-2:]
            if top2[1] - top2[0] > 2 * PREFILL_LOGIT_ATOL:
                _check(out[0] == int(np.argmax(ref)),
                       "first greedy token is the reference argmax")
        _say("prefill_vs_dense_max_abs_diff", worst)
        _check(worst <= PREFILL_LOGIT_ATOL,
               f"prefill logits agree with dense forward to "
               f"{PREFILL_LOGIT_ATOL}")
        stats = eng.stats()
        _say("engine_stats", stats)
    finally:
        eng.shutdown()
    _check(not failed_steps, "no 'engine step failed' was logged")
    return {"device": info, "stats": stats, "max_abs_diff": worst}


def phase_mesh4(args) -> dict:
    """--chips 4: Phase A's program for 3 steps on a mesh of fsdp=2 x
    tensor=2, then on the first device alone, same seed and batch.  The
    losses agree and the parameters really are spread over four devices."""
    _use_default_compile_cache()
    import gc

    import jax
    import numpy as np
    from jax.experimental import mesh_utils

    from ray_tpu.parallel.mesh import AXES, MeshConfig

    cache = _count_cache_events()
    info = _device_report(args.rehearse)
    devices = jax.devices()
    _check(len(devices) == 4, "four devices are visible")
    mc4 = MeshConfig(data=1, fsdp=2, tensor=2)
    shape = tuple(mc4.as_dict()[a] for a in AXES)
    try:
        want = mesh_utils.create_device_mesh(shape, devices=np.asarray(devices))
        branch = "mesh_utils.create_device_mesh"
    except Exception as e:  # noqa: BLE001 - reported, and fails below
        want, branch = None, f"plain reshape ({e!r})"
    _say("build_mesh_branch", branch)
    _check(want is not None,
           "create_device_mesh laid out the four devices (no reshape fallback)")

    cfg, prog, state, b = _build_program(args.rehearse, devices, mc4)
    _check(bool((prog.mesh.devices == want).all()),
           "the program's mesh is create_device_mesh's layout")
    _say("mesh", dict(prog.mesh.shape))
    holders = set()
    for leaf in jax.tree_util.tree_leaves(state.params):
        holders |= {s.device for s in leaf.addressable_shards}
    _check(holders == set(devices),
           "all four devices hold shards of the parameters")
    w = state.params["blocks"]["mlp_in"]["kernel"]   # fsdp x tensor
    _say("mlp_in_kernel", f"{w.shape} sharded {w.sharding.spec}")
    _check(all(s.data.size * 4 == w.size for s in w.addressable_shards)
           and len(w.addressable_shards) == 4,
           "a leaf sharded over both axes holds a quarter per device")
    state, losses4, compile4 = _run_steps(prog, state, b, 3)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    _say("bytes_in_use", in_use)
    if not args.rehearse:      # the CPU backend reports no memory stats
        _check(all(n > 0 for n in in_use),
               "every device has bytes in use")
    _say("compile_s_4chip", round(compile4, 2))
    _say("losses_4chip", losses4)

    del state, prog, b
    gc.collect()
    _, prog, state, b = _build_program(args.rehearse, devices[:1],
                                       MeshConfig(data=1))
    state, losses1, compile1 = _run_steps(prog, state, b, 3)
    _say("compile_s_1chip", round(compile1, 2))
    _say("compile_cache", cache)
    _say("losses_1chip", losses1)
    _check(_finite(losses4 + losses1), "every loss is finite")
    worst = max(abs(a - b) for a, b in zip(losses4, losses1))
    _say("max_abs_loss_diff", worst)
    _check(worst <= MESH4_LOSS_ATOL,
           f"four-chip and one-chip losses agree to {MESH4_LOSS_ATOL}")
    return {"device": info, "losses_4chip": losses4, "losses_1chip": losses1,
            "compile_s": compile4, "compile_s_1chip": compile1}


PHASES = {"native": phase_native, "train": phase_train,
          "framework": phase_framework, "serve": phase_serve,
          "mesh4": phase_mesh4}


# -------------------------------------------------------------------- parent
def _probe() -> None:
    """What this machine offers, seen without jax."""
    import glob
    _say("python", sys.version.split()[0])
    for var in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS",
                "RTPU_NUM_TPUS"):
        _say(var, os.environ.get(var))
    _say("dev_accel", sorted(glob.glob("/dev/accel*")))
    _say("dev_vfio", sorted(glob.glob("/dev/vfio/*")))
    google_pci = []
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        if Path(vendor).read_text().strip() == "0x1ae0":
            google_pci.append(Path(vendor).with_name("device")
                              .read_text().strip())
    _say("google_pci_functions", google_pci)


def _tail_worker_logs(n_lines: int = 60) -> None:
    marker = RUN_DIR / "framework.session"
    if not marker.exists():
        return
    for log in sorted(Path(marker.read_text(), "logs").glob("*.log")):
        print(f"----- tail of {log}", flush=True)
        print("\n".join(log.read_text(errors="replace")
                        .splitlines()[-n_lines:]), flush=True)


def _run_phase(name: str, args, env: dict, timeout_s: float) -> dict:
    """One phase in a child of its own; returns its result or exits."""
    print(f"===== phase {name} (timeout {timeout_s:.0f}s)", flush=True)
    out = RUN_DIR / f"{name}.json"
    cmd = [sys.executable, str(Path(__file__).resolve()), "--phase", name]
    if args.rehearse:
        cmd.append("--rehearse")
    t0 = time.monotonic()
    # a group of its own, so that a timeout takes the phase's workers too
    child = subprocess.Popen(cmd, env=env, cwd=str(HERE),
                             start_new_session=True)
    try:
        rc = child.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        rc = 124
        print(f"FAIL: phase {name} passed its {timeout_s:.0f}s limit",
              flush=True)
    finally:
        try:
            os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        child.wait()
    _say(f"phase_{name}_seconds", round(time.monotonic() - t0, 1))
    if rc != 0 or not out.exists():
        if name == "framework":
            _tail_worker_logs()
        print(f"FAIL: phase {name} exited {rc}", flush=True)
        raise SystemExit(rc or 1)
    return json.loads(out.read_text())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU; never prints a pass")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="(internal) run one phase in this process")
    args = ap.parse_args()

    _say("pid", os.getpid())
    if args.phase:
        result = PHASES[args.phase](args)
        RUN_DIR.mkdir(parents=True, exist_ok=True)
        (RUN_DIR / f"{args.phase}.json").write_text(json.dumps(result))
        return

    _probe()
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    RUN_DIR.mkdir(parents=True)
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["RTPU_NUM_TPUS"] = "1"     # no device node to count here
        if args.chips == 4:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=4")
    if args.chips == 4:
        names = ["mesh4"]
    else:
        if not args.rehearse:   # a rehearsal may share the tree with tests
            # a stale .so would be what runs: the chip tool copies the
            # disk, and the build is cached by mtime
            shutil.rmtree(HERE / "ray_tpu/native/_build", ignore_errors=True)
        names = ["native", "train", "framework", "serve"]
    t0 = time.monotonic()
    results = {}
    for name in names:
        left = TOTAL_BUDGET_S - (time.monotonic() - t0)
        results[name] = _run_phase(name, args, env,
                                   min(PHASE_TIMEOUT_S[name], left))
    for name, r in results.items():
        if "compile_s" in r:
            _say(f"{name}_compile_s", round(r["compile_s"], 2))
    devices = [r["device"] for r in results.values() if "device" in r]
    device = devices[0]
    agreed = all(d == device for d in devices)
    if (agreed and not args.rehearse and device["platform"] == "tpu"
            and device["count"] == args.chips):
        print(json.dumps({"ok": True, "device": device}))
        return
    print(json.dumps({"ok": False, "rehearsal": args.rehearse,
                      "phases_passed": names, "device": device}))
    raise SystemExit(0 if args.rehearse and agreed else 1)


if __name__ == "__main__":
    main()
