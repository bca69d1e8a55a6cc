"""What the harness has to know of the ``afmoe`` family (Arcee Trinity):
how a configuration file's sizes (under their config.json names) name the
program's model configuration, which module of the program holds the
model, and where its plain reference is.

The file states one chip's share of one pipeline stage of a deployment.
``layer_types`` lists the attention of the layers HELD (``reduced`` names
it beside ``num_hidden_layers`` and ``num_dense_layers``), ``held_layers``
the published layers they are, and the file is refused where the one is
not the other read off ``published.layer_types``.  ``num_experts`` counts
the experts HELD in each routed layer, ``published.num_experts`` those the
router scores (its matrix is whole here), ``first_held`` the first of the
share; ``vocab_size`` the rows of the embedding and columns of the head
held.  Every width is as published.

Serving.  The family routes, and says so with ``routed(config_file)``
(``perfbench/README.md``, "A routed family"): the serving job asks the
program's runner for the experts it chose (ids among ALL the router's
experts, held or not) and hands them to ``reference_logits(...,
choices=ids)``.  The selection score is ``sigmoid + expert_bias``; the
weights are the reference's own sigmoids of the program's set, over their
sum + 1e-20, times ``route_scale`` (``reference/afmoe_ref.py``).

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny`` and merges sizes under GPT-2's key names into the
configuration.  A configuration so shrunk (GPT-2's names present) is not
this family's any more: ``check_sizes``, ``routed`` and
``reference_logits`` hand it to ``families/gpt2.py``, as the other served
families do.  ``rehearsal/afmoe.json`` carries this family's own toy
sizes, which ``tests/perfbench/test_perfbench_afmoe.py`` runs through the
same job.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import afmoe_ref

# config.json key -> the attribute of the program's AfmoeConfig
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_dense_layers": "n_dense_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim",
    "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "expert_dim",
    "num_experts": "held",
    "num_experts_per_tok": "experts_per_token",
    "num_shared_experts": "n_shared_experts",
    "route_scale": "route_scale",
    "sliding_window": "sliding_window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "first_held": "first_held",
}
# what the program's block is, and a file must not say otherwise
FIXED = {"model_type": "afmoe", "hidden_act": "silu", "mup_enabled": True,
         "route_norm": True, "score_func": "sigmoid", "rope_scaling": None,
         "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
         "num_expert_groups": 1, "num_limited_groups": 1}


def module():
    from ray_tpu.models import afmoe
    return afmoe


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def held_types(config_file: dict) -> list:
    """The attention of the layers held, in order: ``layer_types``, which
    has to be the published list read at ``held_layers``, the first
    ``num_dense_layers`` of them among the published leading dense ones and
    the rest behind those."""
    held = list(config_file["layer_types"])
    at = list(config_file["held_layers"])
    published = config_file["published"]
    n_dense = config_file["num_dense_layers"]
    if held != [published["layer_types"][i] for i in at] \
            or len(held) != config_file["num_hidden_layers"] \
            or any(i >= published["num_dense_layers"] for i in at[:n_dense]) \
            or any(i < published["num_dense_layers"] for i in at[n_dense:]):
        raise ValueError(
            f"layer_types ({len(held)} layers, num_hidden_layers "
            f"{config_file['num_hidden_layers']}, {n_dense} dense) is not "
            f"layers {at} of the published list")
    return held


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes, the held layers'
    attention and the router's width."""
    out = {k: config_file[k] for k in KEYS}
    out["layer_types"] = held_types(config_file)
    out["router_experts"] = config_file["published"]["num_experts"]
    return out


def sizes_of_model(model_cfg) -> dict:
    """The same settings, read off a program's model configuration."""
    out = {k: getattr(model_cfg, attr) for k, attr in KEYS.items()}
    out["layer_types"] = list(model_cfg.layer_types)
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
        raise ValueError(f"the program's AFMoE block has {FIXED}, and the "
                         f"configuration file says {other}")


def routed(config_file: dict):
    """What the serving check has to be handed by the program: the chosen
    expert ids of every routed layer, int (layers, rows, k), each below
    ``experts``: the router's, of which the held are a share.  None: the
    configuration does not route (a rehearsal)."""
    if shrunk(config_file):
        return None
    return {"layers": config_file["num_hidden_layers"]
            - config_file["num_dense_layers"],
            "k": config_file["num_experts_per_tok"],
            "experts": config_file["published"]["num_experts"]}


def reference_logits(params, tokens, config_file: dict, choices=None):
    """Float32 logits (B, T, V); under the program's ``choices`` (routed
    layers, B x T, k) -> (logits, audit): ``afmoe_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return afmoe_ref.logits(params, tokens, sizes(config_file),
                            choices=choices)
