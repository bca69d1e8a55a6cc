"""What the harness has to know of the ``qwen3_next`` family
(Qwen3-Next-80B-A3B): how a configuration file's sizes (under their
config.json names) become the program's model configuration, which module
of the program holds the model, and where its plain reference and its
operation count are.

The file states one chip's share of a deployment: ``num_experts`` is the
number of routed experts HELD (``published.num_experts`` is the router's
width and ``deployment.held_expert_ids`` says which), ``vocab_size`` the
vocabulary rows held, ``num_hidden_layers`` the stage's layers, whole
periods of ``full_attention_interval``.

The rehearsal (``--rehearse``) merges ``rehearsal/overrides.json`` into the
configuration, and that file names sizes as GPT-2 does (``n_embd``,
``n_layer``, ``n_head``, ``n_positions``; ``vocab_size`` is shared).
``sizes`` takes those keys as overrides: hidden size, attention heads and
positions become theirs, the depth theirs rounded up to a whole period, and
every other width shrinks with the hidden size (the DeltaNet heads to 2 key
and 4 value heads); the router's 512 outputs, 64 held and 10 a token stay.
A model so shrunk is built in float32, as the other routed families' are.
"""

from __future__ import annotations

from perfbench import flops_qwen3_next
from perfbench.reference import qwen3_next_ref

SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
             "num_hidden_layers", "full_attention_interval",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "linear_num_key_heads", "linear_num_value_heads",
             "linear_key_head_dim", "linear_value_head_dim",
             "linear_conv_kernel_dim", "moe_intermediate_size",
             "shared_expert_intermediate_size", "num_experts",
             "num_experts_per_tok")
SETTING_KEYS = ("rms_norm_eps", "rope_theta", "partial_rotary_factor")
WIDTH_KEYS = ("head_dim", "linear_key_head_dim", "linear_value_head_dim",
              "moe_intermediate_size", "shared_expert_intermediate_size")
GPT2_NAMES = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
              "n_head": "num_attention_heads",
              "n_positions": "max_position_embeddings"}
# the blocks the program has (models/qwen3_next.py): any other value of
# these keys is a layer it does not compute
BLOCK = {"model_type": "qwen3_next", "mlp_only_layers": [],
         "use_sliding_window": False, "decoder_sparse_step": 1,
         "tie_word_embeddings": False, "norm_topk_prob": True,
         "rope_scaling": None, "hidden_act": "silu"}
RULE_CHUNK = 64


def module():
    from ray_tpu.models import qwen3_next
    return qwen3_next


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in GPT2_NAMES)


def check_sizes(config_file: dict) -> None:
    """Refuse a file whose block the program does not have, or whose share
    does not add up.  Another ``full_attention_interval`` is fine."""
    wrong = {k: config_file.get(k) for k, v in BLOCK.items()
             if k not in config_file or config_file[k] != v}
    if config_file.get("attention_bias", False):
        wrong["attention_bias"] = True
    if wrong:
        raise ValueError(
            "the program's qwen3_next blocks have no dense-MLP layer, no "
            "sliding window, no layer without experts, no tied head, no "
            "unnormalised top-k, no RoPE scaling, no bias and no activation "
            f"other than silu; the file has {wrong}")
    held = config_file["deployment"]["held_expert_ids"]
    width = config_file["published"]["num_experts"]
    if len(held) != config_file["num_experts"] \
            or held != list(range(held[0], held[0] + len(held))) \
            or not 0 <= held[0] <= held[-1] < width:
        raise ValueError(
            f"num_experts {config_file['num_experts']} must count "
            f"deployment.held_expert_ids {held}, a contiguous range of the "
            f"router's {width}")
    if config_file["num_hidden_layers"] \
            % config_file["full_attention_interval"] \
            and not shrunk(config_file):
        raise ValueError("num_hidden_layers is no whole number of periods "
                         "of full_attention_interval")


def sizes(config_file: dict) -> dict:
    check_sizes(config_file)
    out = {k: config_file[k] for k in SIZE_KEYS + SETTING_KEYS}
    out["router_width"] = config_file["published"]["num_experts"]
    out["held_expert_ids"] = list(config_file["deployment"]["held_expert_ids"])
    out["rule_chunk"] = config_file.get("train", {}).get(
        "model_options", {}).get("rule_chunk", RULE_CHUNK)
    if shrunk(config_file):
        ratio = config_file.get("n_embd", out["hidden_size"]) \
            / config_file["hidden_size"]
        for gpt2_name, name in GPT2_NAMES.items():
            out[name] = config_file.get(gpt2_name, out[name])
        for key in WIDTH_KEYS:
            out[key] = max(8, 2 * int(config_file[key] * ratio / 2))
        period = out["full_attention_interval"]
        out["num_hidden_layers"] = -(-out["num_hidden_layers"] // period) \
            * period
        heads = out["num_attention_heads"]
        out["num_key_value_heads"] = min(out["num_key_value_heads"], heads)
        per_key = config_file["linear_num_value_heads"] \
            // config_file["linear_num_key_heads"]
        out["linear_num_key_heads"] = 2
        out["linear_num_value_heads"] = 2 * per_key
    return out


def model_config(config_file: dict, options: dict):
    """The program's Qwen3NextConfig at the file's sizes; ``options`` are
    the file's assumed training settings (dtypes by name)."""
    import jax.numpy as jnp
    opts = dict(options)
    for key in ("param_dtype", "dtype"):
        if key in opts:
            opts[key] = jnp.float32 if shrunk(config_file) \
                else jnp.dtype(opts[key])
    s = sizes(config_file)
    return module().Qwen3NextConfig(
        vocab_size=s["vocab_size"], max_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], n_layer=s["num_hidden_layers"],
        attn_interval=s["full_attention_interval"],
        n_head=s["num_attention_heads"], n_kv_head=s["num_key_value_heads"],
        head_dim=s["head_dim"],
        rotary_dim=int(s["head_dim"] * s["partial_rotary_factor"]),
        rope_theta=float(s["rope_theta"]),
        gdn_key_heads=s["linear_num_key_heads"],
        gdn_value_heads=s["linear_num_value_heads"],
        gdn_key_dim=s["linear_key_head_dim"],
        gdn_value_dim=s["linear_value_head_dim"],
        conv_kernel=s["linear_conv_kernel_dim"],
        expert_dim=s["moe_intermediate_size"],
        shared_dim=s["shared_expert_intermediate_size"],
        n_routed_experts=s["router_width"], n_held_experts=s["num_experts"],
        first_held_expert=s["held_expert_ids"][0],
        experts_per_token=s["num_experts_per_tok"],
        rms_eps=s["rms_norm_eps"], **opts)


def flops_per_token(config_file: dict, seq_len: int) -> float:
    return flops_qwen3_next.flops_per_token(sizes(config_file), seq_len)


def reference_loss(params, inputs, targets, config_file: dict):
    return qwen3_next_ref.loss(params, inputs, targets, sizes(config_file))


def reference_logits(params, tokens, config_file: dict):
    return qwen3_next_ref.logits(params, tokens, sizes(config_file))
