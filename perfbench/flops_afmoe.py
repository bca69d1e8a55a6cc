"""What an AFMoE (Trinity) prefill chunk has to do, from the
configuration's sizes (config.json names), counted from the layer
equations and not from the implementation: 2 operations a multiply-add.

The sliding layers' attention proper: a query at position ``t`` attends to
``min(t + 1, sliding_window)`` positions, and each costs ``q k^T`` and ``p
v``, ``2 x head_dim`` operations apiece a query head.
``chunk_required_attention_flops`` sums that over the real queries of one
chunk and the sliding layers held: what ``reducers/
prefill_sparse_peak_share.py`` divides by the band kernel's device time.
The kernel computes whole tiles of 128 queries x 512 keys wherever the
band crosses them, so it does at least this and the share cannot pass
100."""

from __future__ import annotations

from perfbench import bytes_afmoe as b

SLIDING, FULL = "sliding_attention", "full_attention"


def _count(sizes: dict, kind: str) -> int:
    return sum(1 for t in sizes["layer_types"] if t == kind)


def attended(position: int, window=None) -> int:
    """Positions the query at ``position`` sees, its own among them."""
    seen = position + 1
    return seen if window is None else min(seen, window)


def _chunk_flops(sizes: dict, start: int, n_tokens: int, chunk: int,
                 kind: str) -> int:
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    window = sizes["sliding_window"] if kind == SLIDING else None
    seen = sum(attended(t, window)
               for t in range(start, min(start + chunk, n_tokens)))
    return _count(sizes, kind) * heads * d * 4 * seen


def chunk_required_attention_flops(sizes: dict, start: int, n_tokens: int,
                                   chunk: int) -> int:
    """The sliding layers' band, for the chunk of a prompt of ``n_tokens``
    that starts at ``start``."""
    return _chunk_flops(sizes, start, n_tokens, chunk, SLIDING)


def chunk_full_attention_flops(sizes: dict, start: int, n_tokens: int,
                               chunk: int) -> int:
    """The full layers' causal attention for the same chunk."""
    return _chunk_flops(sizes, start, n_tokens, chunk, FULL)


def chunk_matmul_flops(sizes: dict, chunk: int) -> int:
    """The projections', the shared expert's, the router's and the dense
    layer's operations for a chunk of positions, and the held experts' at
    the expected share of the k choices that fall among them (the head
    runs for one position and is left out)."""
    e = sizes["hidden_size"]
    routed = b.routed_layers(sizes)
    fixed = sizes["num_dense_layers"] * b.dense_layer_params(sizes) + routed * (
        b.attention_params(sizes) + e * sizes["published"]["num_experts"]
        + sizes["num_shared_experts"] * b.expert_params(sizes))
    held_share = sizes["num_experts"] / sizes["published"]["num_experts"]
    chosen = routed * sizes["num_experts_per_tok"] * held_share \
        * b.expert_params(sizes)
    return int(2 * chunk * (fixed + chosen))
