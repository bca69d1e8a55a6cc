"""The operations a Ling prefill chunk's delta rule REQUIRES, from the
configuration's sizes (config.json names), counted from the chunked form
of the layer's equations (``perfbench/LING.md``) and not from the
implementation.

For one head and one chunk of ``C`` = 64 positions of keys and values
``d`` wide, a multiply and an add two operations::

    A (strictly lower: C (C - 1) / 2 pairs of d channels)    C (C - 1) d
    (I + A)^-1 applied to [beta V | beta K e^G] by forward
    substitution: C (C - 1) / 2 rows of 2 d columns          2 C (C - 1) d
    W S0, (q e^G) S0, (k e^(G_C - G))^T V': three products
    of C x d x d                                             6 C d d
    the scores q . k on and below the diagonal               C (C + 1) d
    scores V'                                                C (C + 1) d

A chunk's padding (positions at and past the prompt's end) requires
nothing.  The implementation does more: it makes the inverse by products
in float32 (``ops/delta_rule.inverse_unit_lower``: 2 (log2 C - 1) products
of C^3 at six bf16 passes each) and whole (C, C) tiles for the halves, so
its share of the peak cannot pass 100."""

from __future__ import annotations

RULE_CHUNK = 64             # models/ling.py's kda_chunk


def kda_layers(sizes: dict) -> int:
    group = sizes["layer_group_size"]
    return sum(1 for i in sizes["held_layers"] if (i + 1) % group)


def rule_chunk_flops(sizes: dict, positions: int = RULE_CHUNK) -> int:
    """One head's required operations for a chunk of ``positions``."""
    c, d = positions, sizes["head_dim"]
    return c * (c - 1) * d + 2 * c * (c - 1) * d + 6 * c * d * d \
        + 2 * c * (c + 1) * d


def chunk_rule_flops(sizes: dict, start: int, tokens: int,
                     chunk: int) -> int:
    """The rule's required operations in one prefill chunk of ``chunk``
    positions from ``start`` of a prompt of ``tokens``: every KDA layer,
    every head, the real positions in whole rule chunks and what is left
    as a shorter one."""
    real = max(0, min(chunk, tokens - start))
    whole, rest = divmod(real, RULE_CHUNK)
    per_head = whole * rule_chunk_flops(sizes) \
        + (rule_chunk_flops(sizes, rest) if rest else 0)
    return kda_layers(sizes) * sizes["num_attention_heads"] * per_head


# the name reducers/prefill_sparse_peak_share.py asks its module for
chunk_required_attention_flops = chunk_rule_flops
