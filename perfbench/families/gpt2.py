"""What the harness has to know of the GPT-2 family: how a configuration
file's sizes become the program's model configuration, which module of
the program holds the model, and where its plain reference and its
operation count are.  A new family is a new file here."""

from __future__ import annotations

from perfbench import flops
from perfbench.reference import gpt2_ref

SIZE_KEYS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")


def module():
    from ray_tpu.models import gpt2
    return gpt2


def sizes(config_file: dict) -> dict:
    return {k: config_file[k] for k in SIZE_KEYS}


def model_config(config_file: dict, options: dict):
    """The program's GPT2Config at the file's sizes; ``options`` are the
    file's assumed training settings (dtypes by name)."""
    import jax.numpy as jnp
    opts = dict(options)
    for key in ("param_dtype", "dtype"):
        if key in opts:
            opts[key] = jnp.dtype(opts[key])
    return module().GPT2Config(**sizes(config_file), **opts)


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's preset must have the file's sizes, or the cell is
    not the configuration it says it is."""
    got = {k: getattr(model_cfg, k) for k in SIZE_KEYS}
    if got != sizes(config_file):
        raise ValueError(f"the program's model has sizes {got} and the "
                         f"configuration file says {sizes(config_file)}")


def flops_per_token(config_file: dict, seq_len: int) -> float:
    return flops.flops_per_token(sizes(config_file), seq_len)


def reference_loss(params, inputs, targets, config_file: dict):
    return gpt2_ref.loss(params, inputs, targets, config_file["n_head"])


def reference_logits(params, tokens, config_file: dict):
    return gpt2_ref.logits(params, tokens, config_file["n_head"])
