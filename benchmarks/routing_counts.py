"""The rows each expert group really gets in a training cell: one run of
``perfbench.run`` with the counts of every routed layer call kept (PR 62).

    chiprun -- python benchmarks/routing_counts.py chiprun_out/counts.json \\
        --workload qwen3-next-80b-a3b.train-b2-s8192 --seed 1 --seconds 51 \\
        --trace 0

The first argument is the JSON file that receives ``counts`` (the distinct
(E,) vectors ``ops/moe._sorted_assignments`` returned, in the order first
seen, every ``ROUTING_COUNTS_EVERY``-th of them: 1 keeps the first 64) and
``steps`` (the ``moe_*`` step metrics of every step: ``moe_held_rows``,
``moe_choice_share_held``, ``moe_tile_fill`` ...); the other arguments are
``perfbench.run``'s.  ``benchmarks/grouped_matmul_bench.py --counts`` takes
the file.  The counts leave the step through ``jax.debug.callback``, so the
program differs from the cell's by those callbacks (30,688 tokens/s/chip
where the cell reads 32,900: not a number of the cell).  A share of the
experts trained alone learns to choose the experts that are there:
``benchmarks/counts/qwen3_next_window.json`` holds four vectors of one
Qwen3-Next window, 20,993 to 147,998 of 163,840 rows on the 64 held.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

out = sys.argv.pop(1)
every = int(os.environ.get("ROUTING_COUNTS_EVERY", "1"))
# as perfbench.run does before jax is imported
os.environ.setdefault("TPU_LOG_DIR", "disabled")
from ray_tpu._private.config import GLOBAL_CONFIG  # noqa: E402

GLOBAL_CONFIG.apply_xla_cache_env(os.environ)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.parallel import spmd  # noqa: E402

seen_counts: dict = {}
seen_steps: list = []
sorted_assignments = moe._sorted_assignments
build_train_program = spmd.build_train_program


def keep(group_sizes) -> None:
    key = tuple(int(v) for v in np.asarray(group_sizes))
    seen_counts[key] = seen_counts.get(key, 0) + 1


def counting(expert_idx, weights, num_experts, first_held):
    res = sorted_assignments(expert_idx, weights, num_experts, first_held)
    jax.debug.callback(keep, res[3])
    return res


def build(*args, **kwargs):
    prog = build_train_program(*args, **kwargs)

    def step(state, batch):
        state, metrics = prog.step_fn(state, batch)
        seen_steps.append({k: v for k, v in metrics.items()
                           if k.startswith("moe_")})
        return state, metrics
    return dataclasses.replace(prog, step_fn=step)


moe._sorted_assignments = counting
spmd.build_train_program = build

from perfbench import run  # noqa: E402

try:
    run.main()
finally:
    order = list(seen_counts)           # a dict keeps the order first seen
    kept = order[:64] if every == 1 else order[::every]
    steps = [{k: float(jax.device_get(v)) for k, v in m.items()}
             for m in seen_steps]
    with open(out, "w") as sink:
        json.dump({"counts": [list(k) for k in kept],
                   "seen": [seen_counts[k] for k in kept],
                   "distinct": len(order), "every": every,
                   "steps": steps}, sink)
