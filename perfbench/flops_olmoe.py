"""Operations an OLMoE train step needs per token, from the sizes alone.

6 x the matrix parameters a token touches (forward and the two backward
products): the four attention projections, the router, the token's k
experts of three matrices each, and the untied head; not the embedding,
which is a lookup.  Plus the attention scores and values, as
perfbench/flops.py counts them: 2 matmuls x 2 FLOPs x 3 x L x E x T over
the whole sequence (causal skipping is the kernel's saving, not fewer
operations needed by the definition in use here).  Recomputed operations
(remat) do not count.  Sizes are under their config.json names.
"""

from __future__ import annotations


def matmul_params_per_token(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied by, by part."""
    e, layers = sizes["hidden_size"], sizes["num_hidden_layers"]
    d = e // sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"] * d
    return {
        "attention": layers * (2 * e * e + 2 * e * kv),
        "router": layers * e * sizes["num_experts"],
        "experts": layers * sizes["num_experts_per_tok"] * 3 * e
        * sizes["intermediate_size"],
        "head": e * sizes["vocab_size"],
    }


def expert_flops_per_token(sizes: dict) -> float:
    """Forward and backward of the experts' three matmuls alone."""
    return 6.0 * matmul_params_per_token(sizes)["experts"]


def flops_per_token(sizes: dict, seq_len: int) -> float:
    scores = 12 * sizes["num_hidden_layers"] * sizes["hidden_size"] * seq_len
    return 6.0 * sum(matmul_params_per_token(sizes).values()) + scores
