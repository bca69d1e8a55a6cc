"""Operations a train step of the Qwen3-Next share needs per token, by
part, from the sizes alone.

As perfbench/flops_kanana.py counts: 6 x the matrix parameters a token is
multiplied by (forward and the two backward products), not the embedding,
which is a lookup; plus the attention scores and values of the full
attention layers over the whole sequence, 2 FLOPs x 3 x layers x H x (D +
D) x T (causal skipping is the kernel's saving, not fewer operations
needed); plus the gated delta rule's products.  Recomputed operations
(remat) and padding do not count.

The delta rule is counted in the chunked form at the program's chunk C
(``rule_chunk``), a value head and a chunk, in multiply-adds: the (C, C)
products k . k and q . k once a KEY head (C C dk each, shared by the R
value heads that read it); the unit lower triangular solve by
substitution, C^3 / 3 (the program's inverse by repeated squaring spends
about thirty times that and none of the excess is counted); T applied to
[beta V | beta e^c K], C C (dv + dk); the three products against the
entering state, 3 C dk dv; the scores on the corrections, C C dv.  At C =
64 and 128-wide heads that is 83,285 a token and value head, forward; x 2
FLOPs x 3 for the backward.  The token-by-token recurrence needs 3 dk dv =
49,152 and cannot use a matrix unit; a larger chunk needs more.

Of a token's k routed experts only those held here are computed, and
which they are is the router's choice: the count is the expectation under
even routing, k x held / routed of an expert a token (1.25 at 10 x 64 /
512).  ``moe_choice_share_held`` in the step's metrics says how even it
was.

Sizes are under their config.json names; ``num_experts`` is the number
held and ``router_width`` the number the router chooses among.
"""

from __future__ import annotations


def layer_counts(sizes: dict) -> tuple:
    """(Gated DeltaNet layers, full attention layers)."""
    full = sizes["num_hidden_layers"] // sizes["full_attention_interval"]
    return sizes["num_hidden_layers"] - full, full


def matmul_params_per_token(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied by, by part (the conv's
    taps among them: a multiply-add a tap and channel)."""
    e, layers = sizes["hidden_size"], sizes["num_hidden_layers"]
    gdn, full = layer_counts(sizes)
    kw = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    vw = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    expert = 3 * e * sizes["moe_intermediate_size"]
    held_share = sizes["num_experts"] / sizes["router_width"]
    return {
        "gdn_projections": gdn * (e * (2 * kw + 2 * vw)
                                  + e * 2 * sizes["linear_num_value_heads"]
                                  + vw * e),
        "gdn_conv": gdn * sizes["linear_conv_kernel_dim"] * (2 * kw + vw),
        "attention": full * (e * heads * 2 * d + 2 * e * kv * d
                             + heads * d * e),
        "router": layers * e * sizes["router_width"],
        "shared_expert": layers * (
            3 * e * sizes["shared_expert_intermediate_size"] + e),
        "held_experts": layers * sizes["num_experts_per_tok"] * held_share
        * expert,
        "head": e * sizes["vocab_size"],
    }


def rule_macs_per_token(sizes: dict) -> float:
    """Multiply-adds of the chunked gated delta rule a token, forward, over
    all the DeltaNet layers' value heads (the module's head)."""
    c = sizes["rule_chunk"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    h = sizes["linear_num_value_heads"]
    r = h // sizes["linear_num_key_heads"]
    a_head = 2 * c * dk / r + c * c / 3 + c * (dv + dk) + 3 * dk * dv + c * dv
    return layer_counts(sizes)[0] * h * a_head


def rule_flops_per_token(sizes: dict) -> float:
    """Forward and backward of the delta rule's products."""
    return 6.0 * rule_macs_per_token(sizes)


def attention_flops_per_token(sizes: dict, seq_len: int,
                              causal: bool = False) -> float:
    """Scores and values of the full attention layers, forward and
    backward, at the head width as published (256 keys, 256 values): over
    the whole sequence (the step's count), or with ``causal`` over the
    (T + 1) / 2 keys a query may see on average: what a causal kernel has
    to compute, the count for its share of the peak."""
    keys = (seq_len + 1) / 2 if causal else seq_len
    return 6.0 * layer_counts(sizes)[1] * sizes["num_attention_heads"] \
        * 2 * sizes["head_dim"] * keys


def held_expert_flops_per_token(sizes: dict) -> float:
    """Forward and backward of the held routed experts' three matmuls, in
    expectation under even routing."""
    return 6.0 * matmul_params_per_token(sizes)["held_experts"]


def flops_per_token(sizes: dict, seq_len: int) -> float:
    return 6.0 * sum(matmul_params_per_token(sizes).values()) \
        + attention_flops_per_token(sizes, seq_len) \
        + rule_flops_per_token(sizes)
