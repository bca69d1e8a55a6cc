"""What the training cell's loss check is worth for a routed model, on the
chip: ``kanana-2-30b-a3b.train-b2-s8192``'s state trained as the job
trains it (same seed, ring and steps), then on the job's own check
sequences

- the program's loss (bf16, kernels) and the float32 reference's,
- how many of the reference's routing decisions (a token's 6th against its
  7th selection score, per sparse layer) lie within a given width of a
  flip, and what the reference's loss moves when all of those are flipped
  (``kanana_ref``'s ``flip_margin``),
- the reference's loss with every matrix rounded to float8_e4m3: the
  nearest precision below the configuration's, which the job's tolerance
  (``perfbench/jobs/train.LOSS_ATOL``) must fail.

    chiprun -- python benchmarks/kanana_check.py --seed 7 --steps 47

Prints one JSON line and appends it to ``chiprun_out/kanana_check.jsonl``.
Fails off the chip: what bf16 does to a loss is the chip's arithmetic.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CELL = "kanana-2-30b-a3b.train-b2-s8192"
WIDTHS = (2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=47,
                    help="train steps before the check (a 51 s window's)")
    ap.add_argument("--flip-width", type=float, default=1e-3)
    args = ap.parse_args()

    from ray_tpu._private.config import GLOBAL_CONFIG
    import os
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import manifest, traffic
    from perfbench.jobs.train import LOSS_ATOL
    from perfbench.reference import kanana_ref
    from ray_tpu.parallel import mesh as mesh_lib, spmd
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        raise SystemExit(f"kanana_check measures a TPU; this is "
                         f"{jax.default_backend()!r}")
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    config, spec = cell["config_file"], cell["traffic_file"]
    fam = manifest.family(config["family"])
    mod, options = fam.module(), config["train"]
    model_cfg = fam.model_config(config, options["model_options"])
    mc = MeshConfig(**options["mesh"]).resolved(1)
    mesh = mesh_lib.build_mesh(mc, jax.devices()[:1])
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: mod.loss_fn(p, b, model_cfg),
        init_params_fn=lambda rng: mod.init_params(rng, model_cfg),
        optimizer=spmd.default_optimizer(moments_dtype=jnp.dtype(
            options["optimizer"]["moments_dtype"])),
        mesh=mesh, mesh_config=mc)
    state = prog.init_fn(jax.random.key(traffic.key_seed(args.seed)))
    tokens = traffic.train_batches(spec, config["vocab_size"], args.seed)
    ring = [spmd.shard_batch(prog, {"inputs": t[:, :-1], "targets": t[:, 1:]})
            for t in tokens]
    held = []
    for i in range(args.steps):
        state, metrics = prog.step_fn(state, ring[i % len(ring)])
        held.append(metrics["moe_choice_share_held"])
    held = [float(x) for x in jax.device_get(held)]
    params = state.params
    del state
    n = spec["check_sequences"]
    sample = {"inputs": tokens[0][:n, :-1], "targets": tokens[0][:n, 1:]}

    def program_loss(p, b):
        with mesh_lib.ambient_mesh(mesh):
            return mod.loss_fn(p, b, model_cfg)

    got = float(jax.jit(program_loss)(params, spmd.shard_batch(prog, sample)))
    sizes = fam.sizes(config)
    want, margins = kanana_ref.loss_and_margins(
        params, sample["inputs"], sample["targets"], sizes)
    want, margins = float(want), np.asarray(margins)
    flipped = float(kanana_ref.loss(params, sample["inputs"],
                                    sample["targets"], sizes,
                                    flip_margin=args.flip_width))

    def to_float8(path, a):
        """Rounded on the host: on the device XLA drops a convert to a
        narrower type and back (``xla_allow_excess_precision``), and the
        "float8" tree would be the bf16 one."""
        key = jax.tree_util.keystr(path)
        if "scale" in key or "select_bias" in key:
            return a
        host = np.asarray(a.astype(jnp.float32))
        return jnp.asarray(host.astype(jnp.float8_e4m3fn)
                           .astype(np.float32)).astype(a.dtype)

    low = jax.tree_util.tree_map_with_path(to_float8, params)
    changed = float(jnp.abs(low["lm_head"]["kernel"].astype(jnp.float32)
                            - params["lm_head"]["kernel"].astype(jnp.float32)
                            ).max())
    assert changed > 0, "the float8 tree is the bf16 tree"
    float8 = float(kanana_ref.loss(low, sample["inputs"], sample["targets"],
                                   sizes))
    row = {
        "seed": args.seed, "steps": args.steps, "loss_atol": LOSS_ATOL,
        "program_loss": got, "reference_loss": want,
        "loss_abs_diff": abs(got - want),
        "decisions": int(margins.size),
        "decisions_within": {str(w): int((margins < w).sum())
                             for w in WIDTHS},
        "margin_median": float(np.median(margins)),
        "flip_width": args.flip_width, "reference_loss_flipped": flipped,
        "flipped_abs_diff": abs(flipped - want),
        "reference_loss_float8_e4m3": float8,
        "float8_abs_diff_to_program": abs(float8 - got),
        "float8_abs_diff_to_reference": abs(float8 - want),
        "choice_share_held_first_last": [held[0], held[-1]],
    }
    print(json.dumps(row), flush=True)
    out = Path("chiprun_out") / "kanana_check.jsonl"
    out.parent.mkdir(exist_ok=True)
    with out.open("a") as f:
        f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
