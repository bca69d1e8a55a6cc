"""What the harness has to know of the Falcon-H1 family: how a
configuration file's sizes and multipliers (under their config.json names)
name the program's model configuration, which module of the program holds
the model, and where its plain reference is.

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny`` and merges sizes under GPT-2's key names into the
configuration (``rehearsal/overrides.json``).  A configuration so shrunk
(GPT-2's names present) is not this family's any more: ``check_sizes`` and
``reference_logits`` hand it to ``families/gpt2.py``, and the rehearsal of
this family's cell is the serving job's control flow on the toy GPT-2.
The family's own model at a small size goes through the same job in
``tests/perfbench/test_perfbench_falcon_h1.py``, which builds the job's
context itself.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import falcon_h1_ref

# config.json key -> the attribute of the program's FalconH1Config
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim",
    "intermediate_size": "ffn_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "mamba_n_heads": "ssm_heads",
    "mamba_d_head": "ssm_head_dim",
    "mamba_d_ssm": "d_ssm",
    "mamba_d_state": "ssm_state",
    "mamba_n_groups": "ssm_groups",
    "mamba_d_conv": "conv_width",
    "mamba_chunk_size": "ssm_chunk",
    "embedding_multiplier": "embedding_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "ssm_multipliers": "ssm_multipliers",
    "mlp_multipliers": "mlp_multipliers",
}
# what the program's block is, and a file must not say otherwise
FIXED = {"attention_bias": False, "mamba_conv_bias": True,
         "mamba_proj_bias": False, "mamba_rms_norm": True,
         "mamba_norm_before_gate": False, "mlp_bias": False,
         "projectors_bias": False, "tie_word_embeddings": False,
         "rope_scaling": None, "hidden_act": "silu",
         "attn_layer_indices": None}


def module():
    from ray_tpu.models import falcon_h1
    return falcon_h1


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def _plain(value):
    return [float(v) for v in value] if isinstance(value, (list, tuple)) \
        else value


def sizes(config_file: dict) -> dict:
    return {k: _plain(config_file[k]) for k in KEYS}


def sizes_of_model(model_cfg) -> dict:
    """The same sizes, read off a program's model configuration."""
    return {k: _plain(getattr(model_cfg, attr)) for k, attr in KEYS.items()}


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's preset must have the file's sizes and multipliers,
    and the file a block the program has, or the cell is not the
    configuration it says it is."""
    if shrunk(config_file):
        return gpt2.check_sizes(config_file, model_cfg)
    got, want = sizes_of_model(model_cfg), sizes(config_file)
    if got != want:
        differ = {k: (got[k], want[k]) for k in KEYS if got[k] != want[k]}
        raise ValueError("the program's model and the configuration file "
                         f"differ in (program, file): {differ}")
    other = {k: config_file[k] for k, v in FIXED.items()
             if config_file[k] != v}
    if other:
        raise ValueError(f"the program's Falcon-H1 block has {FIXED}, and "
                         f"the configuration file says {other}")


def reference_logits(params, tokens, config_file: dict):
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return falcon_h1_ref.logits(params, tokens, sizes(config_file))
