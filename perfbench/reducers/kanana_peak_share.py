"""One kind of kernel's share of the chip's peak in the Kanana-2 training
cell, in percent: the operations that kind needs in one step
(perfbench/flops_kanana.py: forward and both backward products, recomputed
operations and padding not counted, x the step's tokens) over the device
time of its kernels in one step (kernel_ms_per_step with the same
``names`` and ``shapes``) and the published bf16 peak.

``part`` is ``attention`` (the scores and values at the key and value
widths as published, over the keys a causal query sees at the sequence
length of ``traffic``) or
``held_experts`` (the grouped matmuls of the routed experts held here, for
the rows that go to them in expectation under even routing).  ``config``
names the configuration file whose sizes are counted."""

import json

from perfbench import flops_kanana, manifest
from perfbench.families import deepseek_v3
from perfbench.reducers import kernel_ms_per_step


def reduce(facts: dict, params: dict):
    ms = kernel_ms_per_step.reduce(facts, params)
    if not ms or not facts.get("peak_flops_per_s"):
        return None
    sizes = deepseek_v3.sizes(
        json.loads((manifest.ROOT / params["config"]).read_text()))
    if params["part"] == "attention":
        seq = json.loads((manifest.ROOT / params["traffic"]).read_text())["seq"]
        per_token = flops_kanana.attention_flops_per_token(
            sizes, seq, causal=True)
    else:
        per_token = flops_kanana.held_expert_flops_per_token(sizes)
    tokens_per_step = facts["tokens"] / facts["steps"] / facts["chips"]
    return 100.0 * per_token * tokens_per_step / (ms * 1e-3) \
        / facts["peak_flops_per_s"]
