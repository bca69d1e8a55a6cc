"""What the harness has to know of the ``nemotron_h`` family
(Nemotron-3-Nano-30B-A3B): how a configuration file's sizes (under their
config.json names) become the program's model configuration, which module
of the program holds the model, and where its plain reference and its
operation count are.

The file states one chip's share of a deployment: ``n_routed_experts`` is
the number of routed experts HELD (``published.n_routed_experts`` is the
router's width and ``deployment.held_expert_ids`` says which),
``vocab_size`` the vocabulary rows held, ``hybrid_override_pattern`` the
stage's layers in their order and ``num_hidden_layers`` their number.

The rehearsal (``--rehearse``) merges ``rehearsal/overrides.json`` into the
configuration, and that file names sizes as GPT-2 does (``n_embd``,
``n_layer``, ``n_head``, ``n_positions``; ``vocab_size`` is shared).
``sizes`` takes those keys as overrides: hidden size, attention heads and
positions become theirs, the pattern stays (its nine layers are what the
loop over kinds has to run), and every other width shrinks with the hidden
size (the mixer to 4 heads on 2 groups); the router's 128 outputs, 16 held
and 6 a token stay.  A model so shrunk is built in float32, as the other
routed families' are.
"""

from __future__ import annotations

from perfbench import flops_nemotron_h
from perfbench.reference import nemotron_h_ref

SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
             "num_hidden_layers", "hybrid_override_pattern",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
             "n_groups", "conv_kernel", "chunk_size",
             "moe_intermediate_size", "moe_shared_expert_intermediate_size",
             "n_routed_experts", "num_experts_per_tok")
SETTING_KEYS = ("layer_norm_epsilon", "routed_scaling_factor")
WIDTH_KEYS = ("head_dim", "mamba_head_dim", "ssm_state_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size")
GPT2_NAMES = {"n_embd": "hidden_size", "n_head": "num_attention_heads",
              "n_positions": "max_position_embeddings"}
# the blocks the program has (models/nemotron_h.py): any other value of
# these keys is a layer it does not compute
BLOCK = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
         "mamba_hidden_act": "silu", "use_conv_bias": True,
         "use_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
         "attention_bias": False, "tie_word_embeddings": False,
         "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
         "n_shared_experts": 1, "sliding_window": None,
         "residual_in_fp32": False}


def module():
    from ray_tpu.models import nemotron_h
    return nemotron_h


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in GPT2_NAMES)


def check_sizes(config_file: dict) -> None:
    """Refuse a file whose block the program does not have, or whose share
    or pattern does not add up."""
    wrong = {k: config_file.get(k, "absent") for k, v in BLOCK.items()
             if k not in config_file or config_file[k] != v}
    if wrong:
        raise ValueError(
            "the program's nemotron_h blocks have no activation but relu2 "
            "in the experts and silu in the mixer, no conv without a bias, "
            "no other bias, no tied head, no unnormalised top-k, no group-"
            "limited routing, one shared expert, no sliding window and no "
            f"float32 residual; the file has {wrong}")
    pattern = config_file["hybrid_override_pattern"]
    if "-" in pattern:
        raise ValueError(f"hybrid_override_pattern {pattern!r} has a dense "
                         "MLP layer ('-'), which the program does not have")
    if not pattern or set(pattern) - set("ME*"):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: a layer is "
                         "M, E or *")
    if len(pattern) != config_file["num_hidden_layers"]:
        raise ValueError(
            f"num_hidden_layers {config_file['num_hidden_layers']} must "
            f"count hybrid_override_pattern {pattern!r}")
    whole = config_file["published"]["hybrid_override_pattern"]
    if pattern not in whole:
        raise ValueError(f"hybrid_override_pattern {pattern!r} is no run of "
                         f"the published {whole!r}")
    if config_file["mamba_num_heads"] % config_file["n_groups"]:
        raise ValueError("n_groups must divide mamba_num_heads")
    held = config_file["deployment"]["held_expert_ids"]
    width = config_file["published"]["n_routed_experts"]
    if len(held) != config_file["n_routed_experts"] \
            or held != list(range(held[0], held[0] + len(held))) \
            or not 0 <= held[0] <= held[-1] < width:
        raise ValueError(
            f"n_routed_experts {config_file['n_routed_experts']} must count "
            f"deployment.held_expert_ids {held}, a contiguous range of the "
            f"router's {width}")


def sizes(config_file: dict) -> dict:
    check_sizes(config_file)
    out = {k: config_file[k] for k in SIZE_KEYS + SETTING_KEYS}
    out["router_width"] = config_file["published"]["n_routed_experts"]
    out["published_layers"] = config_file["published"]["num_hidden_layers"]
    out["held_expert_ids"] = list(config_file["deployment"]["held_expert_ids"])
    if shrunk(config_file):
        ratio = config_file.get("n_embd", out["hidden_size"]) \
            / config_file["hidden_size"]
        for gpt2_name, name in GPT2_NAMES.items():
            out[name] = config_file.get(gpt2_name, out[name])
        for key in WIDTH_KEYS:
            out[key] = max(8, 2 * int(config_file[key] * ratio / 2))
        out["num_key_value_heads"] = min(out["num_key_value_heads"],
                                         out["num_attention_heads"])
        out["mamba_num_heads"], out["n_groups"], out["chunk_size"] = 4, 2, 8
    return out


def model_config(config_file: dict, options: dict):
    """The program's NemotronHConfig at the file's sizes; ``options`` are
    the file's assumed training settings (dtypes by name)."""
    import jax.numpy as jnp
    opts = dict(options)
    for key in ("param_dtype", "dtype"):
        if key in opts:
            opts[key] = jnp.float32 if shrunk(config_file) \
                else jnp.dtype(opts[key])
    s = sizes(config_file)
    return module().NemotronHConfig(
        vocab_size=s["vocab_size"], max_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], pattern=s["hybrid_override_pattern"],
        n_head=s["num_attention_heads"], n_kv_head=s["num_key_value_heads"],
        head_dim=s["head_dim"], ssm_heads=s["mamba_num_heads"],
        ssm_head_dim=s["mamba_head_dim"], ssm_state=s["ssm_state_size"],
        ssm_groups=s["n_groups"], conv_kernel=s["conv_kernel"],
        ssm_chunk=s["chunk_size"], expert_dim=s["moe_intermediate_size"],
        shared_dim=s["moe_shared_expert_intermediate_size"],
        n_routed_experts=s["router_width"],
        n_held_experts=s["n_routed_experts"],
        first_held_expert=s["held_expert_ids"][0],
        experts_per_token=s["num_experts_per_tok"],
        routed_scale=float(s["routed_scaling_factor"]),
        rms_eps=s["layer_norm_epsilon"], init_depth=s["published_layers"],
        **opts)


def flops_per_token(config_file: dict, seq_len: int) -> float:
    return flops_nemotron_h.flops_per_token(sizes(config_file), seq_len)


def reference_loss(params, inputs, targets, config_file: dict):
    return nemotron_h_ref.loss(params, inputs, targets, sizes(config_file))


def reference_logits(params, tokens, config_file: dict):
    return nemotron_h_ref.logits(params, tokens, sizes(config_file))
