"""Operations a train step of the Nemotron-H share needs per token, by
part, from the sizes alone.

As perfbench/flops_qwen3_next.py counts: 6 x the matrix parameters a token
is multiplied by (forward and the two backward products), not the
embedding, which is a lookup; plus the Mamba-2 scan's own products; plus
the attention layers' scores and values.  Recomputed operations (remat)
and padding do not count.

The scan is counted in the chunked form at the program's chunk Q
(``chunk_size``), a token and head, in multiply-adds: the scores ``C . B``
once a GROUP (Q N, shared by the H / G heads that read it), the scores on
the chunk's inputs (Q P), what the chunk adds to the carried state (N P)
and the entering state read by each position (N P).  At Q = 128, N = 128, P
= 64 and 8 heads a group that is 26,624 a token and head, forward; x 2
FLOPs x 3 for the backward.  The token-by-token recurrence needs 2 N P =
16,384 and cannot use a matrix unit.  The program's products ask for
float32 at ``HIGHEST`` (six bf16 passes each) and its decays are float32
elementwise passes; none of that excess is counted.

The attention's scores and values are counted over the (T + 1) / 2 keys a
causal query sees on average, here in the step's count too (ISSUE 73's
arithmetic).  The older cells' counts (flops_kanana.py,
flops_qwen3_next.py) take the whole sequence in the step's count and the
causal half only for the kernel's share: ``attention_flops_per_token(...,
causal=False)`` gives that count, and PERF.md says what the cell's
``train_program.mfu`` would read under it.

Of a token's k routed experts only those held here are computed, and
which they are is the router's choice: the count is the expectation under
even routing, k x held / routed of an expert a token (0.75 at 6 x 16 /
128).  ``moe_choice_share_held`` in the step's metrics says how even it
was.

Sizes are under their config.json names; ``n_routed_experts`` is the
number held and ``router_width`` the number the router chooses among.
"""

from __future__ import annotations


def layer_counts(sizes: dict) -> dict:
    """Layers by kind: {"M", "E", "*"}."""
    pattern = sizes["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def matmul_params_per_token(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied by, by part (the conv's
    taps among them: a multiply-add a tap and channel)."""
    e, layers = sizes["hidden_size"], layer_counts(sizes)
    d_ssm = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv = d_ssm + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    expert = 2 * e * sizes["moe_intermediate_size"]
    held_share = sizes["n_routed_experts"] / sizes["router_width"]
    return {
        "mamba_projections": layers["M"] * (
            e * (d_ssm + conv + sizes["mamba_num_heads"]) + d_ssm * e),
        "mamba_conv": layers["M"] * sizes["conv_kernel"] * conv,
        "attention": layers["*"] * (e * heads * d + 2 * e * kv * d
                                    + heads * d * e),
        "router": layers["E"] * e * sizes["router_width"],
        "shared_expert": layers["E"] * 2 * e
        * sizes["moe_shared_expert_intermediate_size"],
        "held_experts": layers["E"] * sizes["num_experts_per_tok"]
        * held_share * expert,
        "head": e * sizes["vocab_size"],
    }


def scan_macs_per_token(sizes: dict) -> float:
    """Multiply-adds of the chunked scan a token, forward, over all the
    Mamba-2 layers' heads."""
    q, n, p = (sizes["chunk_size"], sizes["ssm_state_size"],
               sizes["mamba_head_dim"])
    heads = sizes["mamba_num_heads"]
    a_head = q * n / (heads // sizes["n_groups"]) + q * p + 2 * n * p
    return layer_counts(sizes)["M"] * heads * a_head


def scan_flops_per_token(sizes: dict) -> float:
    """Forward and backward of the scan's products."""
    return 6.0 * scan_macs_per_token(sizes)


def attention_flops_per_token(sizes: dict, seq_len: int,
                              causal: bool = True) -> float:
    """Scores and values of the attention layers, forward and backward,
    at 128-wide keys and values: over the (T + 1) / 2 keys a causal query
    sees on average (the step's count here, and a causal kernel's), or
    with ``causal`` false over the whole sequence (the older cells' step
    count)."""
    keys = (seq_len + 1) / 2 if causal else seq_len
    return 6.0 * layer_counts(sizes)["*"] * sizes["num_attention_heads"] \
        * 2 * sizes["head_dim"] * keys


def held_expert_flops_per_token(sizes: dict) -> float:
    """Forward and backward of the held routed experts' two matmuls, in
    expectation under even routing."""
    return 6.0 * matmul_params_per_token(sizes)["held_experts"]


def parts_flops_per_token(sizes: dict, seq_len: int) -> dict:
    """The step's count by part, forward and backward."""
    parts = {k: 6.0 * v for k, v in matmul_params_per_token(sizes).items()}
    parts["mamba_scan"] = scan_flops_per_token(sizes)
    parts["attention_scores"] = attention_flops_per_token(sizes, seq_len)
    return parts


def flops_per_token(sizes: dict, seq_len: int) -> float:
    return sum(parts_flops_per_token(sizes, seq_len).values())
