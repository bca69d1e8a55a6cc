"""Bytes of recurrent state a Falcon-H1 decode step has to move, from the
configuration's sizes (config.json names).

A sequence holds, in every layer, the scan's state (heads x head size x
state size) and the conv's tail (width - 1 inputs of the conv's channels:
x and the groups' B and C), in float32.  One decode step reads and writes
the whole of it for every row it steps and nothing can be skipped: the
state after token t is a function of all of the state before it.  So the
least a step moves is ``2 x rows x bytes a row``, at the memory's
bandwidth; the projections that feed the update (a few KB a row) and the
store's rows nobody steps are not counted."""

from __future__ import annotations

STATE_ITEMSIZE = 4          # float32, as the configuration's `assumed` says


def state_bytes_per_row(sizes: dict) -> int:
    """One sequence's recurrent state over all the layers held here."""
    scan = (sizes["mamba_n_heads"] * sizes["mamba_d_head"]
            * sizes["mamba_d_state"])
    conv_channels = sizes["mamba_d_ssm"] \
        + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    tail = (sizes["mamba_d_conv"] - 1) * conv_channels
    return sizes["num_hidden_layers"] * (scan + tail) * STATE_ITEMSIZE


def decode_state_bytes(sizes: dict, rows: int) -> int:
    """What one decode step over ``rows`` sequences must read and write."""
    return 2 * rows * state_bytes_per_row(sizes)
