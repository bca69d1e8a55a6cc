"""What the harness has to know of the ``ling`` family (Ling 3.0, the
language model of Ling-3.0-flash-VL): how a configuration file's sizes
(under their config.json names) name the program's model configuration,
which module of the program holds the model, and where its plain reference
is.

The file states one chip's share of one pipeline stage of a deployment.
``num_hidden_layers`` counts the layers HELD and ``held_layers`` names the
published layers they are; their mixers follow from the published rule
(layer ``i`` is MLA where ``(i + 1) % layer_group_size == 0``, else KDA:
:func:`held_mixers`), the first ``first_k_dense_replace`` of them among the
published leading dense layers and the rest behind those.  ``num_experts``
counts the experts HELD in each routed layer, ``published.num_experts``
those the router scores (its matrix is whole here), ``first_held`` the
first of the share: one routing group of the published ``n_group``.
``vocab_size``: the rows of the embedding and columns of the head held.
Every width is as published.

Serving.  The family routes, and says so with ``routed(config_file)``
(``perfbench/README.md``, "A routed family"): the serving job asks the
program's runner for the experts it chose (ids among ALL the router's
experts, held or not) and hands them to ``reference_logits(...,
choices=ids)``.  The selection is group-limited (``n_group`` /
``topk_group``), and so is the reference's audit of it
(``reference/ling_ref.py``).

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny`` and merges sizes under GPT-2's key names into the
configuration.  A configuration so shrunk (GPT-2's names present) is not
this family's any more: ``check_sizes``, ``routed`` and
``reference_logits`` hand it to ``families/gpt2.py``, as the other served
families do.  ``rehearsal/ling.json`` carries this family's own toy sizes,
which ``tests/perfbench/test_perfbench_ling.py`` runs through the same job.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import ling_ref

# config.json key -> the attribute of the program's LingConfig
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "first_k_dense_replace": "n_dense_layer",
    "num_attention_heads": "n_head",
    "head_dim": "head_dim",
    "short_conv_kernel_size": "conv_kernel",
    "kda_lower_bound": "kda_lower_bound",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "expert_dim",
    "num_experts": "held",
    "num_experts_per_tok": "experts_per_token",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "routed_scaling_factor": "route_scale",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "first_held": "first_held",
}
# what the program's block is, and a file must not say otherwise
FIXED = {"q_lora_rank": None, "score_function": "sigmoid",
         "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
         "linear_silu": True, "kda_safe_gate": True, "no_kda_lora": True,
         "use_kda_lora": False, "group_norm_size": 1,
         "num_kv_heads_for_linear_attn": 0, "use_qk_norm": True,
         "gated_attention_proj_granularity_type": "head_wise",
         "use_nGPT": False, "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False, "mtp_use_kda": False}


def module():
    from ray_tpu.models import ling
    return ling


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def held_mixers(config_file: dict) -> list:
    """The mixers of the layers held, in order, read off the published
    rule at ``held_layers``; refused where the held layers are not
    ``num_hidden_layers`` published layers in rising order with the dense
    ones leading, or a held layer carries a SwiGLU limit."""
    at = list(config_file["held_layers"])
    published = config_file["published"]
    n_dense = config_file["first_k_dense_replace"]
    group = config_file["layer_group_size"]
    if len(at) != config_file["num_hidden_layers"] or at != sorted(set(at)) \
            or at[-1] >= published["num_hidden_layers"] \
            or any(i >= published["first_k_dense_replace"]
                   for i in at[:n_dense]) \
            or any(i < published["first_k_dense_replace"]
                   for i in at[n_dense:]):
        raise ValueError(
            f"held_layers {at} are not {config_file['num_hidden_layers']} "
            f"published layers, {n_dense} dense ones leading")
    limits = [config_file[key][i] for i in at for key in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list")]
    if any(limits):
        raise ValueError(f"a held layer of {at} has a SwiGLU limit, which "
                         "the program does not compute")
    return ["mla" if (i + 1) % group == 0 else "kda" for i in at]


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes, the held layers'
    mixers and the router's width."""
    out = {k: config_file[k] for k in KEYS}
    out["mixer_types"] = held_mixers(config_file)
    out["router_experts"] = config_file["published"]["num_experts"]
    return out


def sizes_of_model(model_cfg) -> dict:
    """The same settings, read off a program's model configuration."""
    out = {k: getattr(model_cfg, attr) for k, attr in KEYS.items()}
    out["mixer_types"] = list(model_cfg.mixer_types)
    out["router_experts"] = model_cfg.n_experts
    return out


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's preset must have the file's sizes and the file a block
    the program has, or the cell is not the configuration it says it is."""
    if shrunk(config_file):
        return gpt2.check_sizes(config_file, model_cfg)
    got, want = sizes_of_model(model_cfg), sizes(config_file)
    if got != want:
        differ = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError("the program's model and the configuration file "
                         f"differ in (program, file): {differ}")
    other = {k: config_file.get(k, "absent") for k, v in FIXED.items()
             if config_file.get(k, "absent") != v}
    if other:
        raise ValueError(f"the program's Ling block has {FIXED}, and the "
                         f"configuration file says {other}")


def routed(config_file: dict):
    """What the serving check has to be handed by the program: the chosen
    expert ids of every routed layer, int (layers, rows, k), each below
    ``experts``: the router's, of which the held are a share.  None: the
    configuration does not route (a rehearsal)."""
    if shrunk(config_file):
        return None
    return {"layers": config_file["num_hidden_layers"]
            - config_file["first_k_dense_replace"],
            "k": config_file["num_experts_per_tok"],
            "experts": config_file["published"]["num_experts"]}


def reference_logits(params, tokens, config_file: dict, choices=None):
    """Float32 logits (B, T, V); under the program's ``choices`` (routed
    layers, B x T, k) -> (logits, audit): ``ling_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return ling_ref.logits(params, tokens, sizes(config_file),
                           choices=choices)
