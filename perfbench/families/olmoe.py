"""What the harness has to know of the OLMoE family: how a configuration
file's sizes (under their config.json names) become the program's model
configuration, which module of the program holds the model, and where its
plain reference and its operation count are.

The rehearsal (``--rehearse``) merges ``rehearsal/overrides.json`` into the
configuration, and that file names sizes as GPT-2 does (``n_embd``,
``n_layer``, ``n_head``, ``n_positions``; ``vocab_size`` is shared).
``sizes`` takes those keys as overrides: the hidden size, depth, heads and
positions become theirs and the expert width shrinks with the hidden size,
64 experts and 8 a token stay.  A model so shrunk is built in float32: the
rehearsal checks the loss on 2 x 32 tokens, too few for bf16 routing flips
to average out, and a rehearsal is control flow; the chip decides `correct`
at the published widths in the precision the configuration states.

Serving.  The family routes, and says so with ``routed(config_file)``: the
serving job (``jobs/serve.py``) then asks the program's runner for the
experts it chose, hands them to ``reference_logits(..., choices=ids)`` and
holds the audit to the configuration's ``serve.route_margin`` and
``serve.route_differing_share`` (``perfbench/README.md``, "A routed
family").  A rehearsal's serving engine is the toy GPT-2 (GPT-2's names
present), which does not route: ``check_sizes``, ``routed`` and
``reference_logits`` hand such a configuration to ``families/gpt2.py``, as
Falcon-H1's family does.
"""

from __future__ import annotations

from perfbench import flops_olmoe
from perfbench.families import gpt2
from perfbench.reference import olmoe_ref

SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
             "intermediate_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "num_experts", "num_experts_per_tok")
SETTING_KEYS = ("rms_norm_eps", "rope_theta", "router_aux_loss_coef",
                "router_z_loss_coef")
GPT2_NAMES = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
              "n_head": "num_attention_heads",
              "n_positions": "max_position_embeddings"}
# config.json key -> the attribute of the program's LlamaConfig
ATTRS = {"vocab_size": "vocab_size",
         "max_position_embeddings": "max_positions", "hidden_size": "n_embd",
         "intermediate_size": "ffn_dim",
         "num_hidden_layers": "n_layer", "num_attention_heads": "n_head",
         "num_key_value_heads": "n_kv_head", "num_experts": "n_experts",
         "num_experts_per_tok": "experts_per_token",
         "rms_norm_eps": "rms_eps", "rope_theta": "rope_theta"}


def module():
    from ray_tpu.models import llama
    return llama


def shrunk(config_file: dict) -> bool:
    return any(k in config_file for k in GPT2_NAMES)


def sizes(config_file: dict) -> dict:
    out = {k: config_file[k] for k in SIZE_KEYS + SETTING_KEYS}
    if shrunk(config_file):
        width = config_file["intermediate_size"] / config_file["hidden_size"]
        for gpt2_name, name in GPT2_NAMES.items():
            out[name] = config_file.get(gpt2_name, out[name])
        out["num_key_value_heads"] = out["num_attention_heads"]
        out["intermediate_size"] = max(8, int(out["hidden_size"] * width))
    return out


def model_config(config_file: dict, options: dict):
    """The program's LlamaConfig at the file's sizes; ``options`` are the
    file's assumed training settings (dtypes by name)."""
    import jax.numpy as jnp
    opts = dict(options)
    for key in ("param_dtype", "dtype"):
        if key in opts:
            opts[key] = jnp.float32 if shrunk(config_file) \
                else jnp.dtype(opts[key])
    s = sizes(config_file)
    if config_file["norm_topk_prob"] or config_file["clip_qkv"] is not None \
            or config_file["attention_bias"] \
            or config_file["tie_word_embeddings"] \
            or config_file["rope_scaling"] is not None \
            or config_file["hidden_act"] != "silu":
        raise ValueError("the program's OLMoE block has no renormalised "
                         "gates, clipping, biases, tied head, RoPE scaling "
                         "or activation other than silu")
    return module().LlamaConfig(
        vocab_size=s["vocab_size"], max_positions=s["max_position_embeddings"],
        n_embd=s["hidden_size"], n_layer=s["num_hidden_layers"],
        n_head=s["num_attention_heads"], n_kv_head=s["num_key_value_heads"],
        ffn_dim=s["intermediate_size"], rope_theta=float(s["rope_theta"]),
        rms_eps=s["rms_norm_eps"], qk_norm=True, n_experts=s["num_experts"],
        experts_per_token=s["num_experts_per_tok"],
        router_aux_coef=s["router_aux_loss_coef"],
        router_z_coef=s["router_z_loss_coef"], scaled_residual_init=False,
        **opts)


def flops_per_token(config_file: dict, seq_len: int) -> float:
    return flops_olmoe.flops_per_token(sizes(config_file), seq_len)


def reference_loss(params, inputs, targets, config_file: dict):
    return olmoe_ref.loss(params, inputs, targets, sizes(config_file))


def check_sizes(config_file: dict, model_cfg) -> None:
    """The program's serving preset must have the file's sizes and the
    OLMoE block, or the cell is not the configuration it says it is."""
    if shrunk(config_file):
        return gpt2.check_sizes(config_file, model_cfg)
    want = sizes(config_file)
    got = {k: getattr(model_cfg, attr) for k, attr in ATTRS.items()}
    differ = {k: (got[k], want[k]) for k in ATTRS if got[k] != want[k]}
    if differ or not model_cfg.qk_norm:
        raise ValueError("the program's model and the configuration file "
                         f"differ in (program, file): {differ}"
                         + ("" if model_cfg.qk_norm else "; no QK-norm"))


def routed(config_file: dict):
    """What the serving check has to be handed by the program: the chosen
    expert ids of every routed layer, int (layers, rows, k), each below
    ``experts``.  None: the configuration does not route (a rehearsal)."""
    if shrunk(config_file):
        return None
    return {"layers": config_file["num_hidden_layers"],
            "k": config_file["num_experts_per_tok"],
            "experts": config_file["num_experts"]}


def reference_logits(params, tokens, config_file: dict, choices=None):
    """Float32 logits (B, T, V); under the program's ``choices`` (layers,
    B x T, k) -> (logits, audit): ``olmoe_ref.logits``."""
    if shrunk(config_file):
        return gpt2.reference_logits(params, tokens, config_file)
    return olmoe_ref.logits(params, tokens, sizes(config_file),
                            choices=choices)
