"""The operations an EvaByte prefill chunk's attention REQUIRES, from the
configuration's sizes (config.json names) and the mathematics of its layer
(``perfbench/EVABYTE.md``), whatever implements it.

A query attends to the positions of its own window up to its own and to
every folded row of the windows that have closed.  A pair of a query and a
key it sees, exact or folded, is, in every query head, ``q . k`` over
``head_dim`` lanes and ``p v`` over as many, a multiply and an add two
operations: ``4 x num_attention_heads x head_dim`` = 16,384 a pair.  The
pairs are the program's own count (``rows_read`` on ``llm.prefill.chunk``:
``128 W + (t - 2048 W) + 1`` a real query and layer, from the chunk's own
positions), never an expectation.  The fold's own products (a window's K
against ``phi``, the weighted sums) are bound by bytes and are not counted
here.

Today's kernel is a causal flash pass in the coordinates of the rows held,
which computes the tiles the causal frontier crosses whole and masks their
upper halves: its share of the peak on these operations is read low for
that, which is the reading wanted."""

from __future__ import annotations


def pair_flops(sizes: dict) -> int:
    """One query against one key it sees, every query head."""
    return 4 * sizes["num_attention_heads"] \
        * (sizes["hidden_size"] // sizes["num_attention_heads"])


def unit_flops(sizes: dict) -> int:
    """``attribute_peak_share``'s unit: the operations of one counted pair."""
    return pair_flops(sizes)
