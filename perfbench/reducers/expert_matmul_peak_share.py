"""The experts' matmuls as a share of the chip's peak, in percent: the
operations they need in one step (perfbench/flops_olmoe.py: forward and
both backward products of a token's experts, recomputed operations not
counted, x the step's tokens) over the device time of the experts' kernels
in one step (kernel_ms_per_step with the same ``names`` and ``shapes``) and
the published bf16 peak.  ``config`` names the configuration file whose
experts are counted."""

import json

from perfbench import flops_olmoe, manifest
from perfbench.families import olmoe
from perfbench.reducers import kernel_ms_per_step


def reduce(facts: dict, params: dict):
    ms = kernel_ms_per_step.reduce(facts, params)
    if not ms or not facts.get("peak_flops_per_s"):
        return None
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    per_token = flops_olmoe.expert_flops_per_token(olmoe.sizes(config))
    tokens_per_step = facts["tokens"] / facts["steps"] / facts["chips"]
    return 100.0 * per_token * tokens_per_step / (ms * 1e-3) \
        / facts["peak_flops_per_s"]
