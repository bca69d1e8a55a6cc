"""Model FLOP/s utilization: the operations the forward and backward
passes need per token (perfbench/flops.py; recomputed operations do not
count) x tokens per second per chip, over the chip's published bf16 peak
(perfbench/peaks.json), in percent."""


def reduce(facts: dict, params: dict):
    if not facts.get("peak_flops_per_s") or not facts.get("window_s"):
        return None
    per_chip = facts["tokens"] / facts["window_s"] / facts["chips"]
    return 100.0 * per_chip * facts["flops_per_token"] \
        / facts["peak_flops_per_s"]
