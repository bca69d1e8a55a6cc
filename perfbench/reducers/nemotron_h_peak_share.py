"""One part's share of the chip's peak in the Nemotron-H training cell, in
percent: the operations that part needs in one step
(perfbench/flops_nemotron_h.py: forward and both backward products,
recomputed operations and padding not counted, x the step's tokens) over
its device time in one step and the published bf16 peak.

``part`` is ``scan`` (the Mamba-2 scan's products in the chunked form,
over the device time of everything under ``scopes``: keyed on the scope,
so that a Pallas kernel under the same scope is read by the same file),
``attention`` (the scores and values of the attention layers over the
keys a causal query sees at the sequence length of ``traffic``) or
``held_experts`` (the two grouped matmuls of the routed experts held
here, for the rows that go to them in expectation under even routing);
the two last over the kernels of ``names`` and ``shapes``
(kernel_ms_per_step).  ``config`` names the configuration file whose sizes
are counted."""

import json

from perfbench import flops_nemotron_h, manifest
from perfbench.families import nemotron_h
from perfbench.reducers import kernel_ms_per_step, scope_ms_per_step


def reduce(facts: dict, params: dict):
    timer = scope_ms_per_step if params["part"] == "scan" \
        else kernel_ms_per_step
    ms = timer.reduce(facts, params)
    if not ms or not facts.get("peak_flops_per_s"):
        return None
    sizes = nemotron_h.sizes(
        json.loads((manifest.ROOT / params["config"]).read_text()))
    if params["part"] == "scan":
        per_token = flops_nemotron_h.scan_flops_per_token(sizes)
    elif params["part"] == "attention":
        seq = json.loads((manifest.ROOT / params["traffic"]).read_text())["seq"]
        per_token = flops_nemotron_h.attention_flops_per_token(sizes, seq)
    else:
        per_token = flops_nemotron_h.held_expert_flops_per_token(sizes)
    tokens_per_step = facts["tokens"] / facts["steps"] / facts["chips"]
    return 100.0 * per_token * tokens_per_step / (ms * 1e-3) \
        / facts["peak_flops_per_s"]
