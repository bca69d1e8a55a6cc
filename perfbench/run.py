"""Run one cell of BENCHMARK.json once and print the result as the last line.

    python -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics with the device's busy time
and a breakdown.  Without the chips the cell asks for the run ends with
no result; ``--rehearse`` runs the same control flow at a toy size on the
CPU and prints every metric under a ``cpu_rehearsal.`` name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:       # `python perfbench/run.py` works too
    sys.path.insert(0, str(ROOT))

from perfbench import manifest  # noqa: E402

SCRATCH = ROOT / ".perfbench_scratch"       # traces of the run, git-ignored


def _rehearsal_cell(cell: dict) -> dict:
    """The cell at the toy size of rehearsal/overrides.json."""
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    config = {**cell["config_file"], **over["config"]}
    if "train" in config:
        config["train"] = {**config["train"], "model_options": {
            **config["train"]["model_options"], **over["train_model_options"]}}
    if "serve" in config:
        config["serve"] = {**config["serve"], "engine": {
            **config["serve"]["engine"], **over["serve_engine"]}}
    kind = cell["traffic_file"]["kind"]
    traffic = {**cell["traffic_file"], **over["traffic"][kind]}
    return {**cell, "config_file": config, "traffic_file": traffic}


def _prepare_jax(chips: int, rehearse: bool) -> None:
    """Before jax is imported: the compile cache at a fixed path inside
    the checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    kept in it however quickly it compiled.  A rehearsal keeps out of the
    cache: its CPU programs are of no use there."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
        return
    from ray_tpu._private.config import GLOBAL_CONFIG
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def prepare(args):
    """(manifest, cell, the job's context) for one run of one cell."""
    from perfbench import device

    bench = manifest.load_manifest()
    cell = manifest.load_cell(bench, args.workload)
    if args.rehearse:
        cell = _rehearsal_cell(cell)
    _prepare_jax(cell["chips"], args.rehearse)
    marks = {"imports_s": time.perf_counter() - T_START}
    devices = device.claim_devices(cell["chips"], args.rehearse)
    marks["devices_s"] = time.perf_counter() - T_START
    trace_dir = SCRATCH / f"trace-{cell['name']}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {**cell, "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "rehearse": args.rehearse,
           "notes": bool(getattr(args, "notes", "")),
           "devices": devices, "t_start": T_START, "marks": marks,
           "trace_dir": str(trace_dir),
           "peaks": None if args.rehearse
           else device.peaks_for(devices[0].device_kind)}
    return bench, cell, ctx


def run_cell(args) -> dict:
    from perfbench import device, trace

    bench, cell, ctx = prepare(args)
    devices, trace_dir = ctx["devices"], ctx["trace_dir"]
    facts = manifest.job(cell["traffic_file"]["kind"]).run(ctx)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_of_cell(bench, group, cell["name"]):
        spec = manifest.metric_spec(group, m["name"])
        value = manifest.reducer(spec["reducer"])(facts, spec["params"])
        if value is not None:
            name = f"cpu_rehearsal.{m['name']}" if args.rehearse else m["name"]
            metrics[name] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(facts["correct"]),
              "attempted": facts["attempted"], "failed": facts["failed"],
              "metrics": metrics, "device": device.describe(devices)}
    traced = facts.get("trace")
    if traced:
        start, end = traced["window"]
        result["device"]["busy_s"] = trace.busy_seconds(traced)
        result["device"]["window_s"] = end - start
        result["breakdown"] = {"device_ops": trace.top_ops(traced),
                               "idle_gaps": trace.idle_gaps(traced)}
    # what `correct` compared, each number beside its limit: the last key
    # of the line (the driver's record of a run at fault keeps the line's end)
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit)
                          in facts.get("compared", {}).items()}
    if args.notes:
        notes = {"checks": facts["checks"], **facts.get("notes", {}),
                 "setup_marks_s": ctx["marks"],
                 "memory_stats": devices[0].memory_stats()}
        if traced:
            notes["trace_layout"] = traced["layout"]
            notes["longest_device_gaps"] = trace.longest_gaps(traced)
            start = traced["window"][0]
            notes["trace_sample"] = trace.clip_to_window(
                traced, start, start + 0.6)
        out = Path(args.notes)
        out.parent.mkdir(parents=True, exist_ok=True)
        with out.open("a") as f:
            f.write(json.dumps({"workload": cell["name"], "seed": args.seed,
                                "trace": args.trace, "result": result,
                                "notes": notes}) + "\n")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU: control flow only")
    ap.add_argument("--notes", default="",
                    help="(builder) append the run's checks and diagnostics "
                         "to this JSON-lines file")
    args = ap.parse_args(argv)
    result = run_cell(args)
    sys.stdout.flush()
    for name, pair in result["compared"].items():   # stderr's last lines
        print(f"compared {name} = {pair['value']!r} limit {pair['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
