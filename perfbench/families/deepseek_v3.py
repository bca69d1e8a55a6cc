"""What the harness has to know of the ``deepseek_v3`` family (Kanana-2):
how a configuration file's sizes (under their config.json names) become
the program's model configuration, which module of the program holds the
model, and where its plain reference and its operation count are.

The file states one chip's share of a deployment: ``n_routed_experts`` is
the number of routed experts HELD (``published.n_routed_experts`` is the
router's width and ``deployment.held_expert_ids`` says which),
``vocab_size`` the vocabulary rows held, ``num_hidden_layers`` the stage's
layers.

The rehearsal (``--rehearse``) merges ``rehearsal/overrides.json`` into the
configuration, and that file names sizes as GPT-2 does (``n_embd``,
``n_layer``, ``n_head``, ``n_positions``; ``vocab_size`` is shared).
``sizes`` takes those keys as overrides: hidden size, depth, heads and
positions become theirs and every other width shrinks with the hidden
size; the dense prefix, the router's 128 outputs, 16 held and 6 a token
stay.  A model so shrunk is built in float32, as the OLMoE family's is: a
rehearsal is control flow, and 2 x 32 tokens are too few for bf16 routing
flips to average out.
"""

from __future__ import annotations

from perfbench import flops_kanana
from perfbench.reference import kanana_ref

SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
             "intermediate_size", "moe_intermediate_size",
             "num_hidden_layers", "first_k_dense_replace",
             "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "kv_lora_rank", "n_routed_experts",
             "n_shared_experts", "num_experts_per_tok")
SETTING_KEYS = ("rms_norm_eps", "rope_theta", "routed_scaling_factor")
WIDTH_KEYS = ("intermediate_size", "moe_intermediate_size",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "kv_lora_rank")
GPT2_NAMES = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
              "n_head": "num_attention_heads",
              "n_positions": "max_position_embeddings"}
# the one block the program has (models/deepseek_v3.py): any other value
# of these keys is a layer it does not compute
BLOCK = {"model_type": "deepseek_v3", "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "norm_topk_prob": True,
         "rope_scaling": None, "rope_interleave": True,
         "attention_bias": False, "tie_word_embeddings": False,
         "hidden_act": "silu", "moe_layer_freq": 1}


def module():
    from ray_tpu.models import deepseek_v3
    return deepseek_v3


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in GPT2_NAMES)


def check_sizes(config_file: dict) -> None:
    """Refuse a file whose block the program does not have, or whose share
    does not add up."""
    wrong = {k: config_file.get(k) for k, v in BLOCK.items()
             if config_file.get(k, v) != v or k not in config_file}
    if wrong:
        raise ValueError(
            "the program's deepseek_v3 block has no query latent, no group "
            "step, no softmax or loss-balanced router, no unnormalised top-k, "
            "no RoPE scaling or half rotation, no bias, no tied head and no "
            f"activation other than silu; the file has {wrong}")
    held = config_file["deployment"]["held_expert_ids"]
    width = config_file["published"]["n_routed_experts"]
    if len(held) != config_file["n_routed_experts"] \
            or held != list(range(held[0], held[0] + len(held))) \
            or not 0 <= held[0] <= held[-1] < width:
        raise ValueError(
            f"n_routed_experts {config_file['n_routed_experts']} must count "
            f"deployment.held_expert_ids {held}, a contiguous range of the "
            f"router's {width}")
    if config_file["qk_head_dim"] != config_file["qk_nope_head_dim"] \
            + config_file["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")


def sizes(config_file: dict) -> dict:
    check_sizes(config_file)
    out = {k: config_file[k] for k in SIZE_KEYS + SETTING_KEYS}
    out["router_width"] = config_file["published"]["n_routed_experts"]
    out["held_expert_ids"] = list(config_file["deployment"]["held_expert_ids"])
    if shrunk(config_file):
        ratio = config_file.get("n_embd", out["hidden_size"]) \
            / config_file["hidden_size"]
        for gpt2_name, name in GPT2_NAMES.items():
            out[name] = config_file.get(gpt2_name, out[name])
        for key in WIDTH_KEYS:
            out[key] = max(8, 2 * int(config_file[key] * ratio / 2))
        out["num_hidden_layers"] = max(out["num_hidden_layers"],
                                       out["first_k_dense_replace"] + 1)
    return out


def model_config(config_file: dict, options: dict):
    """The program's DeepseekV3Config at the file's sizes; ``options`` are
    the file's assumed training settings (dtypes by name)."""
    import jax.numpy as jnp
    opts = dict(options)
    for key in ("param_dtype", "dtype"):
        if key in opts:
            opts[key] = jnp.float32 if shrunk(config_file) \
                else jnp.dtype(opts[key])
    s = sizes(config_file)
    return module().DeepseekV3Config(
        vocab_size=s["vocab_size"], max_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], n_layer=s["num_hidden_layers"],
        n_dense_layer=s["first_k_dense_replace"],
        n_head=s["num_attention_heads"], qk_nope_dim=s["qk_nope_head_dim"],
        qk_rope_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        kv_latent_dim=s["kv_lora_rank"], ffn_dim=s["intermediate_size"],
        expert_dim=s["moe_intermediate_size"],
        n_routed_experts=s["router_width"],
        n_held_experts=s["n_routed_experts"],
        first_held_expert=s["held_expert_ids"][0],
        experts_per_token=s["num_experts_per_tok"],
        n_shared_experts=s["n_shared_experts"],
        routed_scale=float(s["routed_scaling_factor"]),
        rope_theta=float(s["rope_theta"]), rms_eps=s["rms_norm_eps"], **opts)


def flops_per_token(config_file: dict, seq_len: int) -> float:
    return flops_kanana.flops_per_token(sizes(config_file), seq_len)


def reference_loss(params, inputs, targets, config_file: dict):
    return kanana_ref.loss(params, inputs, targets, sizes(config_file))


def reference_logits(params, tokens, config_file: dict):
    return kanana_ref.logits(params, tokens, sizes(config_file))
