"""What a MiniCPM-SALA decode step has to move and a prefill chunk has to
do, from the configuration's sizes (config.json names), counted from the
layer equations and not from the implementation.

A decode step reads every weight once (bf16), except the embedding, of which
it reads its rows' 4,096 values; for each live row and sparse layer and KV
head the K and V of the pages the selection chose (float32, 64 positions of
128 lanes each: ``page_bytes``), the half-kernels of the row's context (one
of ``num_key_value_heads x head_dim`` float32 every ``kernel_stride``
positions: ``kernel_bytes``), and reads and writes the row's Lightning
state (``lightning_nh x head_dim x head_dim`` float32 a Lightning layer).
A chunk of C positions does 2 operations a weight and position in the
projections and the feed-forward, the recurrence's 2 x 2 x D x D a head
and position, the selection's scores against every kernel of the context
and the attention over the chosen positions."""

from __future__ import annotations

WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says
CACHE_ITEMSIZE = 4          # float32: the pool, the half-kernels, the state
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _count(sizes: dict, kind: str) -> int:
    return sum(1 for m in sizes["mixer_types"] if m == kind)


def layer_params(sizes: dict, kind: str) -> int:
    """One layer's matrices (the norms' few thousand scales left out)."""
    e, f = sizes["hidden_size"], sizes["intermediate_size"]
    ffn = 3 * e * f
    if kind == LIGHTNING:
        hd = sizes["lightning_nh"] * sizes["lightning_head_dim"]
        return 5 * e * hd + ffn                 # W_q, W_k, W_v, W_g, W_o
    hd = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return 3 * e * hd + 2 * e * kv + ffn        # W_q, W_g, W_o; W_k, W_v


def stack_params(sizes: dict) -> int:
    return sum(layer_params(sizes, kind) for kind in sizes["mixer_types"])


def total_params(sizes: dict) -> int:
    """The layers held, the embedding and the untied head."""
    return stack_params(sizes) \
        + 2 * sizes["vocab_size"] * sizes["hidden_size"]


def decode_weight_bytes(sizes: dict) -> int:
    """The weights one decode step reads: the layers and the head."""
    return (stack_params(sizes)
            + sizes["vocab_size"] * sizes["hidden_size"]) * WEIGHT_ITEMSIZE


def page_bytes(sizes: dict) -> int:
    """One page's K and V for one KV head."""
    return 2 * sizes["sparse_config"]["block_size"] * sizes["head_dim"] \
        * CACHE_ITEMSIZE


def kernel_bytes(sizes: dict, context: int) -> int:
    """The half-kernels one row's selection reads in all sparse layers."""
    slots = context // sizes["sparse_config"]["kernel_stride"]
    return _count(sizes, SPARSE) * slots * sizes["num_key_value_heads"] \
        * sizes["head_dim"] * CACHE_ITEMSIZE


def state_bytes_per_row(sizes: dict) -> int:
    """One sequence's Lightning state over all the Lightning layers held."""
    return _count(sizes, LIGHTNING) * sizes["lightning_nh"] \
        * sizes["lightning_head_dim"] ** 2 * CACHE_ITEMSIZE


def decode_state_bytes(sizes: dict, rows: int) -> int:
    """What one decode step over ``rows`` sequences must read and write."""
    return 2 * rows * state_bytes_per_row(sizes)


def chosen_page_bytes(sizes: dict) -> int:
    """The K/V one row reads in all sparse layers once it selects."""
    return _count(sizes, SPARSE) * sizes["num_key_value_heads"] \
        * sizes["sparse_config"]["topk"] * page_bytes(sizes)


def chunk_matmul_flops(sizes: dict, chunk: int) -> int:
    """The projections' and the feed-forwards' operations for a chunk of
    positions (the head runs for one position and is left out)."""
    return 2 * chunk * stack_params(sizes)


def chunk_sparse_attention_flops(sizes: dict, chunk: int,
                                 context: int) -> int:
    """What the equations ask of the sparse layers' attention for a chunk
    whose queries all select, at ``context`` positions: each query head
    against every kernel of the context, and q k^T and p v over the chosen
    ``topk x block_size`` positions."""
    sc = sizes["sparse_config"]
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    kernels = context // sc["kernel_stride"]
    chosen = sc["topk"] * sc["block_size"]
    return _count(sizes, SPARSE) * chunk * heads * d * 2 \
        * (kernels + 2 * chosen)


def chunk_scan_flops(sizes: dict, chunk: int) -> int:
    """The recurrence's own operations: a head's state update and readout,
    2 x D x D each, every position and Lightning layer."""
    d = sizes["lightning_head_dim"]
    return _count(sizes, LIGHTNING) * chunk * sizes["lightning_nh"] \
        * 2 * 2 * d * d


def attended_positions(sizes: dict, position: int) -> int:
    """The positions the equations let the query at ``position`` attend
    to in a sparse layer: all of its ``position + 1`` up to ``dense_len``;
    past it the chosen ``topk`` blocks, of which its own holds the
    positions up to itself."""
    sc = sizes["sparse_config"]
    if position + 1 <= sc["dense_len"]:
        return position + 1
    return (sc["topk"] - 1) * sc["block_size"] \
        + position % sc["block_size"] + 1


def chunk_required_attention_flops(sizes: dict, start: int, n_tokens: int,
                                   chunk: int) -> int:
    """What the sparse layers' attention proper (q k^T and p v, 2 x D
    operations each a head and attended position) must do for the chunk
    of a prompt of ``n_tokens`` that starts at ``start``: its real queries
    over the positions :func:`attended_positions` gives each.  The
    selection's own scores are not in it (``sparse_select``'s)."""
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    attended = sum(attended_positions(sizes, t)
                   for t in range(start, min(start + chunk, n_tokens)))
    return _count(sizes, SPARSE) * heads * d * 4 * attended
