"""What an AFMoE (Trinity) decode step has to move, from the
configuration's sizes (config.json names), counted from the layer
equations and not from the implementation.

A decode step reads every weight outside the experts once (bf16; of the
embedding its rows' ``hidden_size`` values), in every routed layer the
shared expert and each HELD expert that at least one live row chose, whole
(``expert_bytes``: what the program's ``experts_touched`` counts are held
experts only, the share of this chip), and for each live row its K and V:
in the full layers every position of the context, in a sliding layer the
last ``sliding_window`` (float32, ``num_key_value_heads x head_dim`` lanes
a position).  The cache pages K/V in blocks of the engine's ``block_size``
positions, all KV heads of a position side by side, and the decode kernel
copies whole blocks: a *page* here is one block's K and V of one layer,
``page_bytes``, and the pages a step's window layers read are what the
program counts (the attribute ``window_blocks`` of ``llm.decode.pull``:
``reducers/decode_pages_hbm_share.py`` sums them over the traced window),
the window's first block whole although part of it is masked."""

from __future__ import annotations

WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says
CACHE_ITEMSIZE = 4          # float32: both pools
SLIDING, FULL = "sliding_attention", "full_attention"


def _count(sizes: dict, kind: str) -> int:
    return sum(1 for t in sizes["layer_types"] if t == kind)


def routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def attention_params(sizes: dict) -> int:
    """W_q, W_g, W_o and W_k, W_v of one layer."""
    e = sizes["hidden_size"]
    hd = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return 3 * e * hd + 2 * e * kv


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_bytes(sizes: dict) -> int:
    """One expert's three matrices."""
    return expert_params(sizes) * WEIGHT_ITEMSIZE


def dense_layer_params(sizes: dict) -> int:
    return attention_params(sizes) \
        + 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def routed_layer_params(sizes: dict) -> int:
    """Attention, the router over ALL the published experts, the shared
    experts and the experts held."""
    return attention_params(sizes) \
        + sizes["hidden_size"] * sizes["published"]["num_experts"] \
        + (sizes["num_shared_experts"] + sizes["num_experts"]) \
        * expert_params(sizes)


def total_params(sizes: dict) -> int:
    """The layers held, the embedding's rows and the head's columns."""
    return sizes["num_dense_layers"] * dense_layer_params(sizes) \
        + routed_layers(sizes) * routed_layer_params(sizes) \
        + 2 * sizes["vocab_size"] * sizes["hidden_size"]


def decode_fixed_weight_bytes(sizes: dict) -> int:
    """The weights every decode step reads whatever it routes: all but the
    embedding and the held experts."""
    return (total_params(sizes)
            - sizes["vocab_size"] * sizes["hidden_size"]
            - routed_layers(sizes) * sizes["num_experts"]
            * expert_params(sizes)) * WEIGHT_ITEMSIZE


def position_bytes(sizes: dict) -> int:
    """One position's K and V in one layer."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] \
        * CACHE_ITEMSIZE


def page_bytes(sizes: dict) -> int:
    """One block's K and V of one layer, all KV heads: what the decode
    kernel copies for a table column."""
    return sizes["serve"]["engine"]["block_size"] * position_bytes(sizes)


def decode_kv_bytes(sizes: dict, context: int) -> int:
    """The K/V one row at ``context`` positions reads in a decode step,
    all layers: the context in the full layers, the window's part of it
    in the sliding ones."""
    return position_bytes(sizes) * (
        _count(sizes, FULL) * context
        + _count(sizes, SLIDING) * min(context, sizes["sliding_window"]))


def pool_bytes(sizes: dict) -> dict:
    """The two pools of the cell's cache, and what ONE table for all the
    layers would need for the same positions of the full layers' pool."""
    engine = sizes["serve"]["engine"]
    bs = engine["block_size"]
    full = engine["num_blocks"] * bs
    columns = -(-sizes["sliding_window"] // bs) + 1
    window = engine["max_num_seqs"] * columns * bs
    a_position = position_bytes(sizes)
    return {"full": _count(sizes, FULL) * full * a_position,
            "window": _count(sizes, SLIDING) * window * a_position,
            "one_table": len(sizes["layer_types"]) * full * a_position}
