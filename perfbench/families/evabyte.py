"""What the harness has to know of the ``evabyte`` family (EvaByte 6.5B: a
byte-level Llama-2-7B stack under EVA's chunked linearised attention, whose
cache keeps the open window exactly and every closed window at a sixteenth):
how a configuration file's sizes (under their config.json names) name the
program's model configuration, which module of the program holds the model,
and where its plain reference is.

The file states one pipeline stage of a deployment: ``num_hidden_layers``
counts the layers HELD and ``published`` the model's; every width, the 32 /
32 heads of 128, ``window_size`` 2,048, ``chunk_size`` 16, ``num_pred_heads``
8 and the 320 rows are as published.

Serving.  The family does not route (``routed`` answers None) and steps by
tokens (``stepping`` answers None): the job's own ``TokenStepping`` runs a
prompt through the runner's chunks and the paged cache, handing both the
positions a sequence has SEEN, and a window closes inside those public calls
(the prompt's first windows folded by its chunks, a window that fills in
decode folded inside ``cache.append_slot``), so the job's check crosses a
close without knowing of one.  The serving programs return head 0's 320
logits, and so does ``reference_logits``; every head's are compared by
``tests/test_evabyte.py`` and ``benchmarks/evabyte_check.py``
(``heads=True``).

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny``; a configuration so shrunk (GPT-2's names present) is not
this family's any more and is handed to ``families/gpt2.py``.
``rehearsal/evabyte.json`` carries this family's own toy sizes, which
``tests/perfbench/test_perfbench_evabyte.py`` runs through the same job.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import evabyte_ref

# config.json key -> the attribute of the program's LlamaConfig
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "intermediate_size": "ffn_dim",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
    "window_size": "eva_window",
    "chunk_size": "eva_chunk",
    "num_pred_heads": "pred_heads",
    "norm_add_unit_offset": "norm_offset",
    "fp32_skip_add": "residual_f32",
}
# what the program's block is, and a file must not say otherwise
FIXED = {"model_type": "evabyte", "attention_class": "eva",
         "attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False, "fp32_logits": True,
         "mixedp_attn": True, "rope_scaling": None}


def module():
    from ray_tpu.models import llama
    return llama


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes."""
    out = {k: config_file[k] for k in KEYS}
    out["rope_theta"] = float(out["rope_theta"])
    return out


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's preset must have the file's sizes, or the cell is not
    the configuration it says it is."""
    if shrunk(config_file):
        return gpt2.check_sizes(config_file, model_cfg)
    want = {k: config_file[k] for k in KEYS}
    got = {k: getattr(model_cfg, attr) for k, attr in KEYS.items()}
    differ = {k: (got[k], want[k]) for k in want
              if got[k] != want[k] and not (
                  k == "rope_theta" and float(got[k]) == float(want[k]))}
    if differ or model_cfg.n_experts or model_cfg.index_topk \
            or model_cfg.qk_norm:
        raise ValueError("the program's model and the configuration file "
                         f"differ in (program, file): {differ}"
                         "; or the preset routes, indexes or norms q and k")
    other = {k: config_file.get(k, "absent") for k, v in FIXED.items()
             if config_file.get(k, "absent") != v}
    if other:
        raise ValueError(f"the program's EvaByte block has {FIXED}, and the "
                         f"configuration file says {other}")


def routed(config_file: dict):
    """A dense model: nothing is chosen."""
    return None


def stepping(config_file: dict):
    """It steps by tokens, and a window closes inside the runner's and the
    cache's public calls: the job's own stepping crosses it."""
    return None


def reference_logits(params, tokens, config_file: dict, heads: bool = False,
                     fault=None):
    """Float32 logits on the host: head 0's (B, T, V), what the serving
    programs return and the job compares; with ``heads`` every head's, (B,
    T, pred_heads, V): ``evabyte_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return evabyte_ref.logits(params, tokens, sizes(config_file),
                              heads=heads, fault=fault)
