"""What the harness has to know of the ``lfm2`` family (LFM2-MoE): how a
configuration file's sizes (under their config.json names) name the
program's model configuration, which module of the program holds the
model, and where its plain reference is.

The file states one pipeline stage of a deployment.  ``layer_types`` is the
published list of all the layers' mixers, copied whole;
``num_hidden_layers`` and ``num_dense_layers`` count the layers HELD, and
``published`` the model's: the stage holds the last ``num_dense_layers`` of
the leading dense layers and the routed layers that follow them, so its
mixers are ``layer_types[first : first + num_hidden_layers]`` with ``first
= published.num_dense_layers - num_dense_layers`` (:func:`held_types`).
Every width, the 64 experts, 4 a token, and the vocabulary are as
published.

Serving.  The family routes, and says so with ``routed(config_file)``
(``perfbench/README.md``, "A routed family"): the serving job asks the
program's runner for the experts it chose and hands them to
``reference_logits(..., choices=ids)``.  The selection score is ``sigmoid +
expert_bias``; the weights are the reference's own sigmoids of the
program's set, over their sum + 1e-6 (``reference/lfm2_ref.py``).

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny`` and merges sizes under GPT-2's key names into the
configuration.  A configuration so shrunk (GPT-2's names present) is not
this family's any more: ``check_sizes``, ``routed`` and
``reference_logits`` hand it to ``families/gpt2.py``, as Falcon-H1's and
OLMoE's families do.  ``rehearsal/overrides.json`` also carries this
family's own toy sizes (``lfm2``), which
``tests/perfbench/test_perfbench_lfm2.py`` runs through the same job.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import lfm2_ref

# config.json key -> the attribute of the program's Lfm2Config
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_dense_layers": "n_dense_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "expert_dim",
    "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "routed_scaling_factor": "routed_scale",
    "conv_L_cache": "conv_width",
    "norm_eps": "rms_eps",
}
# what the program's block is, and a file must not say otherwise
FIXED = {"model_type": "lfm2_moe", "conv_bias": False,
         "norm_topk_prob": True, "use_expert_bias": True}


def module():
    from ray_tpu.models import lfm2
    return lfm2


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def held_types(config_file: dict) -> list:
    """The mixers of the layers held, in order."""
    first = config_file["published"]["num_dense_layers"] \
        - config_file["num_dense_layers"]
    held = config_file["layer_types"][
        first:first + config_file["num_hidden_layers"]]
    if first < 0 or len(held) != config_file["num_hidden_layers"]:
        raise ValueError(
            f"{config_file['num_hidden_layers']} layers from layer {first} "
            f"on are not in the published {len(config_file['layer_types'])}")
    return list(held)


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes, the head size, the
    RoPE base out of its group, and the held layers' mixers."""
    out = {k: config_file[k] for k in KEYS}
    # the published config gives none: hidden_size / num_attention_heads
    out["head_dim"] = config_file.get("head_dim") or (
        config_file["hidden_size"] // config_file["num_attention_heads"])
    out["rope_theta"] = float(config_file["rope_parameters"]["rope_theta"])
    out["layer_types"] = held_types(config_file)
    return out


def sizes_of_model(model_cfg) -> dict:
    """The same settings, read off a program's model configuration."""
    out = {k: getattr(model_cfg, attr) for k, attr in KEYS.items()}
    out["head_dim"] = model_cfg.head_dim
    out["rope_theta"] = float(model_cfg.rope_theta)
    out["layer_types"] = list(model_cfg.layer_types)
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
    other = {k: config_file.get(k) for k, v in FIXED.items()
             if config_file.get(k) != v}
    if other or config_file["rope_parameters"].get("rope_type") != "default":
        raise ValueError(
            f"the program's LFM2 block has {FIXED} and plain RoPE, and the "
            f"configuration file says {other}, "
            f"{config_file['rope_parameters']}")


def routed(config_file: dict):
    """What the serving check has to be handed by the program: the chosen
    expert ids of every routed layer, int (layers, rows, k), each below
    ``experts``.  None: the configuration does not route (a rehearsal)."""
    if shrunk(config_file):
        return None
    return {"layers": config_file["num_hidden_layers"]
            - config_file["num_dense_layers"],
            "k": config_file["num_experts_per_tok"],
            "experts": config_file["num_experts"]}


def reference_logits(params, tokens, config_file: dict, choices=None):
    """Float32 logits (B, T, V); under the program's ``choices`` (routed
    layers, B x T, k) -> (logits, audit): ``lfm2_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return lfm2_ref.logits(params, tokens, sizes(config_file),
                           choices=choices)
