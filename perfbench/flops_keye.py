"""The operations a Keye-VL-2.0-30B-A3B prefill chunk's attention REQUIRES,
from the configuration's sizes (config.json names) and the mathematics of
its layer (``perfbench/KEYE.md``), whatever implements it.

A query attends to the ``min(t + 1, topk)`` positions it picked and to no
other.  A pair of a query and a chosen key is, in every query head, ``q .
k`` over ``head_dim`` lanes and ``p v`` over as many, a multiply and an add
two operations: ``4 x num_attention_heads x head_dim`` = 16,384 a pair.  The
pairs are the program's own count (``positions_read`` on
``llm.prefill.chunk``: ``min(t + 1, topk)`` a real query and layer, from the
chunk's own positions), never an expectation.  The indexer's own products
(``2 x indexer_num_heads x indexer_head_dim`` a scored pair, over EVERY
pair ``s <= t``) are not attention and are not counted here.

Today's kernel is a flash pass over tiles that computes every pair of a
tile some query of which chose something and masks the pairs not chosen,
so its share of the peak on these operations is low: that is the reading
wanted, and a later kernel that skips what today's masks is read on the
same work."""

from __future__ import annotations


def pair_flops(sizes: dict) -> int:
    """One query against one chosen key, every query head: q . k and p v."""
    return 4 * sizes["num_attention_heads"] * sizes["head_dim"]


def unit_flops(sizes: dict) -> int:
    """``attribute_peak_share``'s unit: the operations of one counted pair."""
    return pair_flops(sizes)


def index_pair_flops(sizes: dict) -> int:
    """One query's index heads against one key: what the indexer adds a
    scored pair (not counted in the attention's share)."""
    sa = sizes["sa_config"]
    return 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
