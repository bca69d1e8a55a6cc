"""What holds Qwen3-Next's mixers to their reference on the chip, where the
training cell's loss check cannot: at random weights of std 0.02 the loss
sits near ln 18,992 whatever the mixers do.  At the published widths and
8,192 positions, on the cell's two check sequences and the program's own
``init_params`` from the seed, the program (bf16, the chunked delta rule,
the flash and grouped-matmul kernels) against
``perfbench/reference/qwen3_next_ref.py`` (float32 at ``highest``, the
rule token by token):

  (i)   layer 0's Gated DeltaNet mixer: its output and the rule's last state;
        and, each alone on the reference's stream after layer 2 (in the
        whole model their part of the stream is small beside what three
        layers of bf16 and of routing have already moved), layer 3's
        attention mixer and layer 0's experts
  (ii)  the residual stream after each of the four layers
  (iii) the logits
  (iv)  the gradient of the loss with respect to layer 0's ``in_proj_qkvz``,
        ``A_log`` and router

each as the root mean square of the difference over the reference's root
mean square (``readings``; the largest difference over the largest value is
reported beside it and not held: in a routed model it is a token whose tenth
and eleventh experts changed places under bf16), beside its tolerance (``TOLERANCES``: three
times the largest reading over seeds 1-6 on the v5e, PERF.md section 6, PR
57).  ``--controls``: the reference with each wrong convention of
``qwen3_next_ref.VARIANTS``, and the program with the rule's products one
precision lower than the configuration's ``assumed`` states (operands
rounded to float8_e4m3 where they are bf16; the solve's float32 operands
rounded to bf16), each against (i)-(iii): how many times its tolerance the
worst comparison reads.

    chiprun -- python benchmarks/qwen3_next_check.py --seed 1 --controls

Prints one JSON line and appends it to ``chiprun_out/qwen3_next_check.jsonl``.
Fails off the chip: what bf16 does to a mixer is the chip's arithmetic.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CELL = "qwen3-next-80b-a3b.train-b2-s8192"
# three times the largest reading of seeds 1-6 on the v5e, to two digits
# (my chip runs, PR 57; the readings are in perfbench/QWEN3_NEXT.md): what
# bf16 activations, the kernels and, from layer 0's experts on, a routed
# model's tokens whose tenth and eleventh experts change places under bf16
# leave between the two sides; None: not measured, report only
TOLERANCES = {
    "mixer_out": 0.018,
    "mixer_state": 0.015,
    "attn_mixer_out": 0.011,
    "experts_out": 0.04,
    "hidden_0": 0.022,
    "hidden_1": 0.039,
    "hidden_2": 0.059,
    "hidden_3": 0.061,
    "logits": 0.061,
    "grad_in_proj_qkvz": 0.098,
    "grad_A_log": 0.16,
    "grad_router": 0.34,
}
FORWARD = ("mixer_out", "mixer_state", "attn_mixer_out", "experts_out",
           "hidden_0", "hidden_1", "hidden_2", "hidden_3", "logits")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--no-gradient", action="store_true")
    args = ap.parse_args()

    from ray_tpu._private.config import GLOBAL_CONFIG
    import os
    GLOBAL_CONFIG.apply_xla_cache_env(os.environ)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from perfbench import manifest, traffic
    from perfbench.reference import qwen3_next_ref as ref
    from ray_tpu.ops import delta_rule

    if jax.default_backend() != "tpu":
        raise SystemExit(f"qwen3_next_check measures a TPU; this is "
                         f"{jax.default_backend()!r}")
    cell = manifest.load_cell(manifest.load_manifest(), CELL)
    config, spec = cell["config_file"], cell["traffic_file"]
    fam = manifest.family(config["family"])
    mod = fam.module()
    cfg = fam.model_config(config, config["train"]["model_options"])
    sizes = fam.sizes(config)
    params = jax.jit(lambda key: mod.init_params(key, cfg))(
        jax.random.key(traffic.key_seed(args.seed)))
    tokens = traffic.train_batches(spec, config["vocab_size"], args.seed)[0]
    n = spec["check_sequences"]
    inputs = jnp.asarray(tokens[:n, :-1], jnp.int32)
    targets = jnp.asarray(tokens[:n, 1:], jnp.int32)
    kinds = [kind for kind, _ in ref.layers_of(params, sizes)]

    def layer_leaves(p, i):
        return ref.layers_of(p, sizes)[i][1]

    # ------------------------------------------------------- the program
    def on_host(tree):
        """Off the device at once: the chip holds the parameters, one
        side's arrays and a gradient's residuals, not every reading."""
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32), tree)

    last = len(kinds) - 1                   # the full attention layer

    @jax.jit
    def program_forward(p, x_in):
        x = p["wte"].astype(cfg.dtype)[inputs]
        lp0, lp_attn = layer_leaves(p, 0), layer_leaves(p, last)
        u = mod._rms0(x, lp0["mixer_norm"]["scale"], cfg.rms_eps)
        mixed, _, state = mod._gdn_mixer(u, lp0, cfg)
        # the two other mixers alone, on the reference's own stream
        x_in = x_in.astype(cfg.dtype)
        attended = mod._attention(mod._rms0(
            x_in, lp_attn["mixer_norm"]["scale"], cfg.rms_eps), lp_attn, cfg)
        routed, _ = mod._experts(mod._rms0(
            x_in, lp0["mlp_norm"]["scale"], cfg.rms_eps), lp0, cfg)
        after = []
        for i, kind in enumerate(kinds):
            x, _ = mod._block(x, layer_leaves(p, i), cfg, kind)
            after.append(x)
        x = mod._rms0(x, p["norm_f"]["scale"], cfg.rms_eps)
        logits = (x @ p["lm_head"]["kernel"].astype(cfg.dtype))
        return mixed, state, attended, routed, after, \
            logits.astype(jnp.float32)

    def named(mixed, state, attended, routed, after, logits):
        return on_host({"mixer_out": mixed, "mixer_state": state,
                        "attn_mixer_out": attended, "experts_out": routed,
                        "logits": logits,
                        **{f"hidden_{i}": a for i, a in enumerate(after)}})

    def program_readables(p, x_in):
        return named(*program_forward(p, x_in))

    # ----------------------------------------------------- the reference
    def reference_readables(variant="", x_in=None):
        """``x_in``: the stream the attention mixer and the experts are
        read on alone (the right reference's after layer 2), or None for
        this reference's own."""
        eps = float(sizes["rms_norm_eps"])
        with jax.default_matmul_precision("highest"):
            lp0, lp_attn = layer_leaves(params, 0), layer_leaves(params, last)
            x = ref._f32(params["wte"])[inputs]
            u = ref._rms0(x, ref._f32(lp0["mixer_norm"]["scale"]), eps,
                          variant)
            mixed, state = ref.mixer(u, lp0, "gdn", sizes, variant)
            after, final = ref.hidden_states(params, inputs, sizes, variant)
            logits = final @ ref._f32(params["lm_head"]["kernel"])
            x_in = after[last - 1] if x_in is None else jnp.asarray(x_in)
            attended, _ = ref.mixer(ref._rms0(
                x_in, ref._f32(lp_attn["mixer_norm"]["scale"]), eps, variant),
                lp_attn, "attn", sizes, variant)
            routed = ref.moe(ref._rms0(
                x_in.reshape(-1, x_in.shape[-1]),
                ref._f32(lp0["mlp_norm"]["scale"]), eps, variant),
                lp0, sizes, variant).reshape(x_in.shape)
        return named(mixed, state, attended, routed, after, logits)

    def distance(got, want):
        """Root mean square of the difference over the reference's root
        mean square: a routed model's LARGEST difference is a token whose
        tenth and eleventh experts changed places under bf16 (an O(1)
        difference in that token, whatever the arithmetic), so the largest
        is reported (``largest``) and not held."""
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        scale = max(np.sqrt(np.mean(want ** 2)), np.sqrt(np.mean(got ** 2)))
        return float(np.sqrt(np.mean((got - want) ** 2)) / scale)

    def largest(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.abs(got - want).max()
                     / max(np.abs(want).max(), np.abs(got).max()))

    def readings(got, want, measure=None):
        return {name: (measure or distance)(got[name], want[name])
                for name in want}

    host_targets = np.asarray(targets)

    def loss_of(logits):
        """The training loss of (B, T, V) float32 logits, on the host."""
        top = logits.max(-1, keepdims=True)
        lse = top[..., 0] + np.log(np.exp(logits - top).sum(-1))
        picked = np.take_along_axis(logits, host_targets[..., None], -1)
        return float((lse - picked[..., 0]).mean())

    right = reference_readables()
    x_in = right[f"hidden_{last - 1}"]
    mine = program_readables(params, x_in)
    row = {"seed": args.seed, "readings": readings(mine, right),
           "largest": readings(mine, right, largest),
           "scales": {k: float(np.abs(np.asarray(v, np.float32)).max())
                      for k, v in right.items()}}

    print(json.dumps({"forward_only": True, **row}), file=sys.stderr,
          flush=True)

    # ------------------------------------------------------- the gradient
    if not args.no_gradient:
        where = {"grad_in_proj_qkvz": ("in_proj_qkvz", "kernel"),
                 "grad_A_log": ("A_log",), "grad_router": ("router", "kernel")}

        def leaf(tree, path):
            for key in path:
                tree = tree[key]
            return tree

        def with_leaves(leaves, dtype, params=params):
            """The tree with layer 0's three leaves replaced (the stacked
            leaf in ``dtype``, or as stored where that is float32)."""
            blocks = dict(params["gdn_blocks"])
            for name, path in where.items():
                stacked = leaf(blocks, path)
                wide = stacked.dtype == jnp.float32
                value = stacked.astype(jnp.float32 if wide else dtype) \
                    .at[0].set(leaves[name].astype(
                        jnp.float32 if wide else dtype))
                if len(path) == 2:
                    blocks[path[0]] = {**blocks[path[0]], path[1]: value}
                else:
                    blocks[path[0]] = value
            return {**params, "gdn_blocks": blocks}

        def leaves_of(dtype):
            return {name: leaf(params["gdn_blocks"], path)[0].astype(dtype)
                    for name, path in where.items()}

        batch = {"inputs": inputs, "targets": targets}
        jax.clear_caches()
        # the tree is an argument: closed over, its 2 GB would be constants
        # of the lowered program
        got = on_host(jax.jit(jax.grad(lambda leaves, p: mod.loss_fn(
            with_leaves(leaves, cfg.param_dtype, p), batch, cfg)))(
            leaves_of(jnp.float32), params))
        jax.clear_caches()
        want = on_host(jax.grad(lambda leaves: ref.loss(
            with_leaves(leaves, jnp.float32), inputs, targets, sizes))(
            leaves_of(jnp.float32)))
        row["readings"].update(readings(got, want))
        row["largest"].update(readings(got, want, largest))
        row["scales"].update({k: float(np.abs(np.asarray(v)).max())
                              for k, v in want.items()})

    from perfbench.jobs.train import LOSS_ATOL
    row["loss_abs_diff"] = abs(loss_of(mine["logits"])
                               - loss_of(right["logits"]))
    row["loss_atol"] = LOSS_ATOL
    row["tolerances"] = TOLERANCES
    row["within"] = {name: value <= TOLERANCES[name]
                     for name, value in row["readings"].items()
                     if TOLERANCES.get(name) is not None}

    # ------------------------------------------------------- the controls
    def worst(got, want):
        """The comparison a control misses by most, in tolerances (in
        readings of this seed where no tolerance is set yet), and what the
        training cell's own check would read of it: the two sides' losses
        apart (its limit is ``jobs/train.LOSS_ATOL``)."""
        read = readings(got, want)
        ratios = {name: value / (TOLERANCES[name] or row["readings"][name])
                  for name, value in read.items() if name in FORWARD}
        ratios = {name: float("inf") if np.isnan(r) else r
                  for name, r in ratios.items()}    # not a number: a miss
        name = max(ratios, key=ratios.get)
        return {"worst": name, "times_its_tolerance": ratios[name],
                "loss_abs_diff": abs(loss_of(got["logits"])
                                     - loss_of(want["logits"]))}

    if args.controls:
        controls = {}
        for variant in ref.VARIANTS:
            controls[variant] = worst(mine, reference_readables(variant, x_in))
        # the program's own products, degraded where the rule makes them:
        # ``_product`` / ``_mm`` in the XLA form, ``_low`` / ``_dot`` in
        # the kernels (module globals, read when the step is traced)
        hooks = ("_product", "_mm", "_low", "_dot")
        kept = {attr: getattr(delta_rule, attr) for attr in hooks}

        def float8_product(spec, a, b, dtype):
            """Operands rounded to float8_e4m3's 3 mantissa bits
            (``reduce_precision`` is an operation XLA keeps)."""
            low = [lax.reduce_precision(x.astype(jnp.float32), 4, 3)
                   for x in (a, b)]
            return kept["_product"](spec, *low, dtype)

        def float8_low(dtype):
            """The kernels' operand cast, through float8_e4m3's values
            first: 3 mantissa bits above 2^-6, steps of 2^-9 below (Mosaic
            has no ``reduce_precision``: the same rounding by bits)."""
            cast, precision = kept["_low"](dtype)

            def e4m3(x):
                x = x.astype(jnp.float32)
                bits = lax.bitcast_convert_type(x, jnp.uint32)
                bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
                return jnp.where(jnp.abs(x) < 2.0 ** -6,
                                 jnp.round(x * 512.0) / 512.0,
                                 lax.bitcast_convert_type(bits, jnp.float32))
            return (lambda x: cast(e4m3(x))), precision

        def bf16_solve(a, b):
            return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)

        def bf16_dot(a, b, contract, precision=None):
            """The kernels' float32 products (``T`` applied, and their
            cotangents) with bf16 operands; the substitution that makes
            ``T`` is float32 arithmetic and stays."""
            if precision is not None:
                a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
            return kept["_dot"](a, b, contract)

        for name, patch in (
                ("rule_products_float8", {"_product": float8_product,
                                          "_low": float8_low}),
                ("rule_solve_bf16", {"_mm": bf16_solve, "_dot": bf16_dot})):
            for attr, fn in patch.items():
                setattr(delta_rule, attr, fn)
            jax.clear_caches()
            controls[name] = worst(program_readables(params, x_in), right)
            for attr, fn in kept.items():
                setattr(delta_rule, attr, fn)
        jax.clear_caches()
        row["controls"] = controls

    print(json.dumps(row), flush=True)
    out = Path("chiprun_out") / "qwen3_next_check.jsonl"
    out.parent.mkdir(exist_ok=True)
    with out.open("a") as f:
        f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
