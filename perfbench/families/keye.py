"""What the harness has to know of the ``keye`` family (Keye-VL-2.0-30B-A3B's
language model: the Qwen3-MoE stack whose attention reads the positions a
lightning indexer picks): how a configuration file's sizes (under their
config.json names, the indexer's under ``sa_config``) name the program's
model configuration, which module of the program holds the model, and where
its plain reference is.

The file states one pipeline stage of a deployment: ``num_hidden_layers``
counts the layers HELD and ``published`` the model's; every width, the 16 x
64 indexer, ``topk`` 2,048, all 128 experts of every layer, 8 a token, and
the whole vocabulary are as published.

Serving.  The family routes (``routed``): the job asks the program's runner
for the experts it chose and hands them to ``reference_logits(...,
choices=ids)``; the selection score is the softmax probability and the
weights are the reference's own probabilities of the program's set over
their sum (``reference/sdar_ref.py``'s router, which ``keye_ref`` shares).
The POSITIONS are not handed over: the reference chooses its own
(``reference/keye_ref.py``), and what a comparison of logits can see of a
wrong choice of positions is written down in ``perfbench/KEYE.md``.  It
steps by tokens: ``stepping`` answers None, and the job's own
``TokenStepping`` runs a prompt through the runner's chunks and the paged
cache's six planes.

The rehearsal (``--rehearse``) sets every serving cell's model to
``gpt2:tiny``; a configuration so shrunk (GPT-2's names present) is not
this family's any more and is handed to ``families/gpt2.py``.
``rehearsal/keye.json`` carries this family's own toy sizes, which
``tests/perfbench/test_perfbench_keye.py`` runs through the same job.
"""

from __future__ import annotations

from perfbench.families import gpt2
from perfbench.reference import keye_ref

# config.json key -> the attribute of the program's LlamaConfig
KEYS = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_positions",
    "hidden_size": "n_embd",
    "num_hidden_layers": "n_layer",
    "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim",
    "moe_intermediate_size": "ffn_dim",
    "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
}
# the file's ``sa_config`` group -> the attribute
INDEXER = {"indexer_num_heads": "index_heads",
           "indexer_head_dim": "index_dim", "topk": "index_topk"}
# what the program's block is, and a file must not say otherwise
FIXED = {"model_type": "KeyeVL2", "attention_bias": False,
         "hidden_act": "silu", "norm_topk_prob": True,
         "decoder_sparse_step": 1, "mlp_only_layers": [],
         "tie_word_embeddings": False, "use_sliding_window": False}


def module():
    from ray_tpu.models import llama
    return llama


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in gpt2.SIZE_KEYS if k != "vocab_size")


def sizes(config_file: dict) -> dict:
    """The reference's settings: the file's sizes and its indexer."""
    out = {k: config_file[k] for k in KEYS}
    out["rope_theta"] = float(out["rope_theta"])
    out["sa_config"] = dict(config_file["sa_config"])
    return out


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's preset must have the file's sizes and its indexer, or
    the cell is not the configuration it says it is."""
    if shrunk(config_file):
        return gpt2.check_sizes(config_file, model_cfg)
    sa = config_file["sa_config"]
    want = {**{k: config_file[k] for k in KEYS}, **{k: sa[k] for k in INDEXER}}
    got = {k: getattr(model_cfg, attr)
           for k, attr in {**KEYS, **INDEXER}.items()}
    differ = {k: (got[k], want[k]) for k in want
              if got[k] != want[k] and not (
                  k == "rope_theta" and float(got[k]) == float(want[k]))}
    block = (model_cfg.qk_norm and model_cfg.qk_norm_heads
             and model_cfg.norm_topk and model_cfg.block_length <= 1)
    if differ or not block or sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's model and the configuration file "
                         f"differ in (program, file): {differ}"
                         + ("" if block else "; the preset lacks the "
                            "per-head QK-norm or the renormalised top-k, or "
                            "steps by blocks")
                         + ("" if sa["indexer_num_kv_heads"] == 1 else
                            "; the program's indexer has one key head"))
    other = {k: config_file.get(k, "absent") for k, v in FIXED.items()
             if config_file.get(k, "absent") != v}
    if other:
        raise ValueError(f"the program's Keye block has {FIXED}, and the "
                         f"configuration file says {other}")


def routed(config_file: dict):
    """What the serving check has to be handed by the program: the chosen
    expert ids of every layer, int (layers, rows, k), each below
    ``experts``.  None: the configuration does not route (a rehearsal)."""
    if shrunk(config_file):
        return None
    return {"layers": config_file["num_hidden_layers"],
            "k": config_file["num_experts_per_tok"],
            "experts": config_file["num_experts"]}


def stepping(config_file: dict):
    """It steps by tokens: the job's own stepping."""
    return None


def reference_logits(params, tokens, config_file: dict, choices=None):
    """Float32 logits (B, T, V) under the reference's own choice of
    positions; under the program's ``choices`` of experts (layers, B x T,
    k) -> (logits, audit): ``keye_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return keye_ref.logits(params, tokens, sizes(config_file),
                           choices=choices)
