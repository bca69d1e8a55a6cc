"""Operations a train step of the Kanana-2 (``deepseek_v3``) share needs
per token, by part, from the sizes alone.

As perfbench/flops_olmoe.py counts: 6 x the matrix parameters a token is
multiplied by (forward and the two backward products), not the embedding,
which is a lookup; plus the attention scores and values over the whole
sequence, 2 FLOPs x 3 x L x H x (key width + value width) x T (causal
skipping is the kernel's saving, not fewer operations needed).  Recomputed
operations (remat) and padding do not count.

Of a token's k routed experts only those held here are computed, and
which they are is the router's choice: the count is the expectation under
even routing, k x held / routed of an expert a token (0.75 at 6 x 16 /
128).  A router that favours the held experts makes the chip do more than
this counts, so a share of the peak computed from it can then read high;
``moe_choice_share_held`` in the step's metrics says how even it was.

Sizes are under their config.json names; ``n_routed_experts`` is the
number held and ``router_width`` the number the router chooses among.
"""

from __future__ import annotations


def matmul_params_per_token(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied by, by part."""
    e, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    layers, dense = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    sparse = layers - dense
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    latent, v = sizes["kv_lora_rank"], sizes["v_head_dim"]
    expert = 3 * e * sizes["moe_intermediate_size"]
    held_share = sizes["n_routed_experts"] / sizes["router_width"]
    return {
        "attention": layers * (e * heads * (nope + rope) + e * (latent + rope)
                               + latent * heads * (nope + v) + heads * v * e),
        "dense_mlp": dense * 3 * e * sizes["intermediate_size"],
        "router": sparse * e * sizes["router_width"],
        "shared_experts": sparse * sizes["n_shared_experts"] * expert,
        "held_experts": sparse * sizes["num_experts_per_tok"] * held_share
        * expert,
        "head": e * sizes["vocab_size"],
    }


def attention_flops_per_token(sizes: dict, seq_len: int,
                              causal: bool = False) -> float:
    """Scores and values, forward and backward, at the key and value
    widths as published (192 and 128 a head): over the whole sequence (the
    step's count, as flops_olmoe.py and flops.py have it), or with
    ``causal`` over the (T + 1) / 2 keys a query may see on average: what
    a causal kernel has to compute, the count for its share of the peak.
    (A share from the whole-sequence count passes 100% as soon as a kernel
    that skips the masked half sustains half the peak.)"""
    widths = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"] \
        + sizes["v_head_dim"]
    keys = (seq_len + 1) / 2 if causal else seq_len
    return 6.0 * sizes["num_hidden_layers"] * sizes["num_attention_heads"] \
        * widths * keys


def held_expert_flops_per_token(sizes: dict) -> float:
    """Forward and backward of the held routed experts' three matmuls, in
    expectation under even routing."""
    return 6.0 * matmul_params_per_token(sizes)["held_experts"]


def flops_per_token(sizes: dict, seq_len: int) -> float:
    return 6.0 * sum(matmul_params_per_token(sizes).values()) \
        + attention_flops_per_token(sizes, seq_len)
