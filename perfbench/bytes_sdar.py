"""Bytes an SDAR-30B-A3B pass has to read, from the configuration's sizes
(config.json names): an expert's weights, and a page of the K/V pool.

An expert is three matrices (``hidden_size x moe_intermediate_size`` twice,
and its transpose), in the serving type, bf16.  A pass over 20-32 live rows
of 4 positions is 640-1,024 assignments over 128 experts, which touches
nearly every expert of every layer; what it has to read is ``touched x
bytes an expert``, where ``touched`` is what the program counted (the
passes' ``experts_touched`` on ``llm.decode.pull``:
``reducers/decode_expert_hbm_share.py``), never an expectation.

A page is one block of the float32 pool in ONE layer, its K and its V:
``2 x block_size x (num_key_value_heads x head_dim) x 4`` bytes.  The block
kernel (``ops/paged_attention._block_decode_kernel``) copies a page's K and
V once a pass for all the block's positions, a KV head's 128 lanes a copy,
so a pass reads ``pages_read x page_bytes`` where ``pages_read`` is the
span's count (``ceil(committed / block_size)`` a row, summed over live rows
and layers: ``reducers/decode_pages_hbm_share.py``)."""

from __future__ import annotations

WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says
POOL_ITEMSIZE = 4           # a float32 pool


def expert_bytes(sizes: dict) -> int:
    """One expert's three matrices."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] \
        * WEIGHT_ITEMSIZE


def routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"]


def page_bytes(sizes: dict) -> int:
    """One page of one layer, K and V, every KV head."""
    return 2 * sizes["serve"]["engine"]["block_size"] \
        * sizes["num_key_value_heads"] * sizes["head_dim"] * POOL_ITEMSIZE
