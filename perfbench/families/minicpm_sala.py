"""What the harness has to know of the ``minicpm_sala`` family
(MiniCPM-SALA): how a configuration file's sizes (under their config.json
names) name the program's model configuration, which module of the program
holds the model, and where its plain reference is.

The file states one stretch of the published layers.  ``mixer_types`` lists
the mixers HELD (``reduced`` names it beside ``num_hidden_layers``),
``published.mixer_types`` the model's 32 and ``held_layers`` the stretch
``[first, end)`` of them: the file is refused where the one is not that
slice of the other.  Every width, both head counts, the feed-forward's
16,384 and the vocabulary are as published.  ``mup_denominator`` is the
published depth, 32: the residual scale ``scale_depth / sqrt(32)`` stays the
model's where fewer layers are held.

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny`` and merges sizes under GPT-2's key names into the
configuration.  A configuration so shrunk (GPT-2's names present) is not
this family's any more: ``check_sizes`` and ``reference_logits`` hand it to
``families/gpt2.py``, as the other served families do.
``rehearsal/minicpm_sala.json`` carries this family's own toy sizes, which
``tests/perfbench/test_perfbench_minicpm_sala.py`` runs through the same job.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import minicpm_sala_ref

# config.json key -> the attribute of the program's MiniCPMSalaConfig
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim",
    "intermediate_size": "ffn_dim",
    "lightning_nh": "lightning_heads",
    "lightning_head_dim": "lightning_head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "scale_emb": "scale_emb",
    "scale_depth": "scale_depth",
    "mup_denominator": "depth_for_scale",
    "dim_model_base": "dim_model_base",
}
# sparse_config key -> the field of the program's SparseSpec
SPARSE_KEYS = {"kernel_size": "kernel", "kernel_stride": "stride",
               "block_size": "block", "init_blocks": "init_blocks",
               "window_size": "window", "topk": "topk",
               "dense_len": "dense_len"}
# what the program's block is, and a file must not say otherwise
FIXED = {"model_type": "minicpm_sala", "attention_bias": False,
         "attn_use_rope": False, "lightning_use_rope": True, "qk_norm": True,
         "hidden_act": "silu", "lightning_scale": "1/sqrt(d)",
         "tie_word_embeddings": False, "use_output_gate": True,
         "use_output_norm": True, "attn_use_output_gate": True,
         "rand_init": False}


def module():
    from ray_tpu.models import minicpm_sala
    return minicpm_sala


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def held_types(config_file: dict) -> list:
    """The mixers of the layers held, in order: ``mixer_types``, which has
    to be the stretch ``held_layers`` of the published list."""
    first, end = config_file["held_layers"]
    held = list(config_file["mixer_types"])
    if held != config_file["published"]["mixer_types"][first:end] \
            or len(held) != config_file["num_hidden_layers"]:
        raise ValueError(
            f"mixer_types ({len(held)} layers, num_hidden_layers "
            f"{config_file['num_hidden_layers']}) is not layers {first}.."
            f"{end - 1} of the published list")
    return held


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes, the held layers' mixers,
    the published depth and the selection's seven sizes."""
    out = {k: config_file[k] for k in KEYS}
    if config_file["lightning_nkv"] != config_file["lightning_nh"]:
        raise ValueError("the Lightning layers' k and v have a head a query "
                         "head (lightning_nkv = lightning_nh)")
    out["mixer_types"] = held_types(config_file)
    out["depth"] = config_file["mup_denominator"]
    out["sparse_config"] = {k: config_file["sparse_config"][k]
                            for k in SPARSE_KEYS}
    return out


def sizes_of_model(model_cfg) -> dict:
    """The same settings, read off a program's model configuration."""
    out = {k: getattr(model_cfg, attr) for k, attr in KEYS.items()}
    out["mixer_types"] = list(model_cfg.mixer_types)
    out["depth"] = model_cfg.depth_for_scale
    out["sparse_config"] = {k: getattr(model_cfg.sparse, attr)
                            for k, attr in SPARSE_KEYS.items()}
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
    if other:
        raise ValueError(f"the program's MiniCPM-SALA block has {FIXED}, and "
                         f"the configuration file says {other}")


def reference_logits(params, tokens, config_file: dict):
    """Float32 logits (B, T, V), on the host: ``minicpm_sala_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return minicpm_sala_ref.logits(params, tokens, sizes(config_file))
