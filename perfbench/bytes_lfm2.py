"""Bytes of expert weights an LFM2-MoE decode step has to read, from the
configuration's sizes (config.json names).

An expert is three matrices (``hidden_size x moe_intermediate_size`` twice,
and its transpose), in the serving type, bf16.  A decode step reads, in
every routed layer, each expert that at least one of its live rows chose,
whole, and no other: the rows are few (32) and the experts many (64 of
9.4 M parameters), so a layer's step is a read of the touched experts'
weights, and which are touched changes with every step.  So the least a
step moves for its experts is ``touched x bytes an expert`` a layer, at the
memory's bandwidth, where ``touched`` is what the program counted (the
steps' ``experts_touched``: ``reducers/decode_expert_hbm_share.py`` sums
them over the traced window and multiplies by ``expert_bytes``), never the
expectation ``64 (1 - (60/64)^rows)``.  The router's matrix (0.26 MB a layer), the rows
themselves and their sort are not counted."""

from __future__ import annotations

WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says


def expert_bytes(sizes: dict) -> int:
    """One expert's three matrices."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] \
        * WEIGHT_ITEMSIZE


def routed_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def decode_expert_bytes(sizes: dict, touched_per_layer: float) -> float:
    """What one decode step must read of the experts' weights, all routed
    layers together, when its rows chose ``touched_per_layer`` distinct
    experts a layer on average."""
    return touched_per_layer * expert_bytes(sizes) * routed_layers(sizes)
