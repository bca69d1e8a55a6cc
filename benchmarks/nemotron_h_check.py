"""The Nemotron-H training cell's comparison with its reference, outside a
window, and the control that shows its limit tells a lower precision.

At the published widths and 8,192 positions, on the cell's two check
sequences and the program's own ``init_params`` from the seed: the
program's loss and logits (bf16, the chunked scan, the flash and
grouped-matmul kernels) against ``perfbench/reference/nemotron_h_ref.py``
(float32 at ``highest``, the recurrence token by token), the loss beside
the training job's ``LOSS_ATOL``, the logits as the root mean square of the
difference over the reference's root mean square, beside ``LOGITS_REL_RMS``:
at random weights a loss is a mean over 16,384 tokens in which a lower
precision averages out, the logits are not.  ``--controls``: the same
reference with EVERY matrix rounded to float8_e4m3, the precision below the
bf16 the configuration states, which has to lie outside the limit.

    chiprun -- python benchmarks/nemotron_h_check.py --seed 1 --controls

Prints one JSON line and appends it to ``chiprun_out/nemotron_h_check.jsonl``.
Fails off the chip: what bf16 does to a loss is the chip's arithmetic.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CELL = "nemotron-3-nano-30b-a3b.train-b2-s8192"
# the logits' relative root mean square against the reference: three times
# the largest reading of the sound program over its seeds on the v5e and a
# third of the float8 control's (PERF.md section 6, PR 73)
LOGITS_REL_RMS = 0.2


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import manifest, traffic
    from perfbench.jobs.train import LOSS_ATOL

    if jax.default_backend() != "tpu":
        sys.exit("benchmarks/nemotron_h_check.py compares the chip's "
                 "arithmetic: run it through chiprun")
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    config, spec = cell["config_file"], cell["traffic_file"]
    fam = manifest.family(config["family"])
    mod = fam.module()
    cfg = fam.model_config(config, config["train"]["model_options"])
    params = jax.jit(lambda rng: mod.init_params(rng, cfg))(
        jax.random.key(traffic.key_seed(args.seed)))
    tokens = traffic.train_batches(spec, config["vocab_size"], args.seed)[0]
    tokens = tokens[:spec["check_sequences"]]
    inputs, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])

    def rel_rms(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    got_loss = float(jax.jit(lambda p: mod.loss_fn(
        p, {"inputs": inputs, "targets": targets}, cfg))(params))
    got_logits = np.asarray(jax.jit(
        lambda p: mod.forward(p, inputs, cfg))(params))
    want_loss = float(fam.reference_loss(params, inputs, targets, config))
    want_logits = np.asarray(fam.reference_logits(params, inputs, config))
    out = {"seed": args.seed, "loss_atol": LOSS_ATOL,
           "program_loss": got_loss, "reference_loss": want_loss,
           "loss_abs_diff": abs(got_loss - want_loss),
           "logits_rel_rms": rel_rms(got_logits, want_logits),
           "logits_rel_rms_limit": LOGITS_REL_RMS}
    out["within"] = out["loss_abs_diff"] <= LOSS_ATOL \
        and out["logits_rel_rms"] <= LOGITS_REL_RMS
    if args.controls:
        # every matrix (two dimensions or more) through float8_e4m3
        low = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, params)
        low_loss = float(fam.reference_loss(low, inputs, targets, config))
        low_logits = np.asarray(fam.reference_logits(low, inputs, config))
        out["float8_reference"] = {
            "loss": low_loss,
            "loss_abs_diff_to_program": abs(got_loss - low_loss),
            "times_the_limit": abs(got_loss - low_loss) / LOSS_ATOL,
            "logits_rel_rms_to_program": rel_rms(got_logits, low_logits)}
        out["float8_reference"]["refused"] = \
            out["float8_reference"]["times_the_limit"] > 1 or out[
                "float8_reference"]["logits_rel_rms_to_program"] \
            > LOGITS_REL_RMS
    line = json.dumps(out)
    path = Path(__file__).resolve().parent.parent / "chiprun_out"
    path.mkdir(exist_ok=True)
    with (path / "nemotron_h_check.jsonl").open("a") as f:
        f.write(line + "\n")
    print(line, flush=True)
    if not out["within"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
