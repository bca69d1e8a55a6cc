"""Operations a GPT-2 train step needs per token, from the sizes alone.

Copied from ``ray_tpu.models.gpt2.flops_per_token`` / ``param_count_analytic``
so that the yardstick does not move when the model file is edited
(tests/perfbench compares the two).  6N for the forward and backward
matmuls of N parameters, plus the attention scores and values:
2 matmuls x 2 FLOPs x 3 (fwd + 2 bwd) x L x E x T.  Recomputed operations
(remat) do not count.
"""

from __future__ import annotations


def param_count(sizes: dict) -> int:
    e, layers = sizes["n_embd"], sizes["n_layer"]
    per_layer = 12 * e * e + 13 * e
    return (sizes["vocab_size"] * e + sizes["n_positions"] * e
            + layers * per_layer + 2 * e)


def flops_per_token(sizes: dict, seq_len: int) -> float:
    attn = 12 * sizes["n_layer"] * sizes["n_embd"] * seq_len
    return 6 * param_count(sizes) + attn
