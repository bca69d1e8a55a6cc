"""What a Ling decode step has to move, from the configuration's sizes
(config.json names), counted from the layer equations and not from the
implementation.

A decode step reads every weight outside the experts once (bf16; of the
embedding its rows' ``hidden_size`` values), in every routed layer the
shared expert and each HELD expert that at least one live row picked,
whole (``expert_bytes``: what the program's ``experts_touched`` counts are
held experts only, one routing group of the published eight); for each
row, in every KDA layer, its state ``S`` (heads x head_dim x head_dim,
float32) and its conv tail (``short_conv_kernel_size - 1`` inputs of q, k
and v), read and written; and in every MLA layer one latent row
``[c | k_rope]`` a position of its context, float32, padded to whole
128-lane tiles (576 -> 640).  The cache pages those rows in blocks of the
engine's ``block_size`` positions and the absorbed kernel copies whole
blocks, once for keys and values: a *page* here is one block of one layer,
``page_bytes``, and the pages a step reads are what the program counts
(the attribute ``latent_pages_read`` of ``llm.decode.pull``:
``reducers/decode_pages_hbm_share.py`` sums them over the traced
window)."""

from __future__ import annotations

WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says
CACHE_ITEMSIZE = 4          # float32: the latent pool and the store
LANES = 128


def mixers(sizes: dict) -> list:
    """The held layers' mixers by the published rule."""
    group = sizes["layer_group_size"]
    return ["mla" if (i + 1) % group == 0 else "kda"
            for i in sizes["held_layers"]]


def routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def kda_params(sizes: dict) -> int:
    """W_q, W_k, W_v, the decay's W_f, W_o, beta and the head-wise gate,
    the conv's taps, A_log, dt_bias and the output norm of one layer."""
    e, h, d = (sizes["hidden_size"], sizes["num_attention_heads"],
               sizes["head_dim"])
    return 5 * e * h * d + 2 * e * h \
        + sizes["short_conv_kernel_size"] * 3 * h * d + h + h * d + d


def mla_params(sizes: dict) -> int:
    """W_q, W_kva, the latent's norm, W_kvb, W_o and the gate."""
    e, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    lora, v = sizes["kv_lora_rank"], sizes["v_head_dim"]
    return e * h * (nope + rope) + e * (lora + rope) + lora \
        + lora * h * (nope + v) + h * v * e + e * h


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_bytes(sizes: dict) -> int:
    """One expert's three matrices."""
    return expert_params(sizes) * WEIGHT_ITEMSIZE


def ffn_params(sizes: dict, routed: bool) -> int:
    """The dense SwiGLU, or the router over ALL the published experts, the
    shared expert and the experts held."""
    e = sizes["hidden_size"]
    if not routed:
        return 3 * e * sizes["intermediate_size"]
    shared = 3 * e * sizes["moe_shared_expert_intermediate_size"]
    return e * sizes["published"]["num_experts"] + shared \
        + sizes["num_experts"] * expert_params(sizes)


def total_params(sizes: dict) -> int:
    """The layers held (their two norms too), the embedding's rows, the
    final norm and the head's columns."""
    e = sizes["hidden_size"]
    total = 2 * sizes["vocab_size"] * e + e
    for i, kind in enumerate(mixers(sizes)):
        total += (kda_params(sizes) if kind == "kda" else mla_params(sizes)) \
            + 2 * e + ffn_params(sizes, i >= sizes["first_k_dense_replace"])
    return total


def decode_fixed_weight_bytes(sizes: dict) -> int:
    """The weights every decode step reads whatever it routes: all but the
    embedding and the held experts."""
    return (total_params(sizes)
            - sizes["vocab_size"] * sizes["hidden_size"]
            - routed_layers(sizes) * sizes["num_experts"]
            * expert_params(sizes)) * WEIGHT_ITEMSIZE


def state_bytes_per_row(sizes: dict) -> int:
    """One sequence's recurrent state over the KDA layers held: ``S`` and
    the conv's tail."""
    h, d = sizes["num_attention_heads"], sizes["head_dim"]
    tail = (sizes["short_conv_kernel_size"] - 1) * 3 * h * d
    return mixers(sizes).count("kda") * (h * d * d + tail) * CACHE_ITEMSIZE


def decode_state_bytes(sizes: dict, rows: int) -> int:
    """What one decode step over ``rows`` sequences must read and write."""
    return 2 * rows * state_bytes_per_row(sizes)


def latent_lanes(sizes: dict) -> int:
    """A cached row's features, padded to whole lanes as the pool holds
    them."""
    f = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    return f + -f % LANES


def page_bytes(sizes: dict) -> int:
    """One block of latent rows of one layer: what the absorbed kernel
    copies for a table column."""
    return sizes["serve"]["engine"]["block_size"] * latent_lanes(sizes) \
        * CACHE_ITEMSIZE


def decode_latent_bytes(sizes: dict, context: int) -> int:
    """The latent rows one row at ``context`` positions reads in a decode
    step, all MLA layers, in whole pages."""
    bs = sizes["serve"]["engine"]["block_size"]
    return mixers(sizes).count("mla") * -(-context // bs) * page_bytes(sizes)


def pool_bytes(sizes: dict) -> dict:
    """The latent pool and the store of the cell's cache."""
    engine = sizes["serve"]["engine"]
    return {"latent": mixers(sizes).count("mla") * engine["num_blocks"]
            * page_bytes(sizes),
            "state": (engine["max_num_seqs"] + 1)
            * state_bytes_per_row(sizes)}
