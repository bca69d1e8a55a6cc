"""Bytes a Keye-VL-2.0-30B-A3B step has to read, from the configuration's
sizes (config.json names) and from the mathematics of its layer
(``perfbench/KEYE.md``), whatever implements it: an expert's weights, a
position's K and V, a position's index key.

An expert is three matrices (``hidden_size x moe_intermediate_size`` twice,
and its transpose), in the serving type, bf16; what a step has to read is
``touched x bytes an expert``, where ``touched`` is what the program counted
(``experts_touched`` on ``llm.decode.pull``:
``reducers/decode_expert_hbm_share.py``), never an expectation.

A position's K and V in one layer are ``2 x num_key_value_heads x head_dim``
float32 lanes of the pool: 4,096 B.  Under the index a query attends to
``min(context, topk)`` positions and to no other, so a step's attention has
to read ``positions_read x 4,096`` bytes, where ``positions_read`` is the
span's count (``min(context, topk)`` a live row and layer, from the step's
own context lengths: ``reducers/decode_pages_hbm_share.py``, whose unit, a
"page", is here ONE position: :func:`page_bytes`).  A walk that copies the
whole pages the chosen positions lie in reads more and its share reads low:
that is the reading wanted.

A position's index key in one layer is ``indexer_head_dim`` float32 lanes,
256 B (``bytes_keye_index.py`` names it as the same reducer's unit): the
score pass has to read every position of a row's context.  The plane holds
a key in a 128-lane row (512 B), so a pass that reads the plane's rows
whole cannot pass 50% of this roofline."""

from __future__ import annotations

WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says
POOL_ITEMSIZE = 4           # float32 pools


def expert_bytes(sizes: dict) -> int:
    """One expert's three matrices."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] \
        * WEIGHT_ITEMSIZE


def routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"]


def position_bytes(sizes: dict) -> int:
    """One position's K and V in one layer, every KV head."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] \
        * POOL_ITEMSIZE


def index_key_bytes(sizes: dict) -> int:
    """One position's index key in one layer."""
    return sizes["sa_config"]["indexer_num_kv_heads"] \
        * sizes["sa_config"]["indexer_head_dim"] * POOL_ITEMSIZE


def page_bytes(sizes: dict) -> int:
    """``decode_pages_hbm_share``'s unit for the walk over chosen
    positions: one position's K and V."""
    return position_bytes(sizes)
