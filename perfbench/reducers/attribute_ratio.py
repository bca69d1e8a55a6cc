"""One attribute of a program span over another, each summed over the spans
named ``span`` that end inside the traced window, x ``scale``: a ratio the
program states of its own work, span by span (``rows_read`` over
``positions_seen`` on ``llm.decode.pull``: what a folded cache leaves of a
decode step's read), read from the run's capture as
``decode_expert_hbm_share.attribute_sum`` reads one attribute.

None where there is no capture, no such span or no such attribute (the
parent of the PR that added them), or the denominator never moved."""

from perfbench import op_scopes
from perfbench.reducers import decode_expert_hbm_share


def reduce(facts: dict, params: dict):
    joined = op_scopes.of_run(facts)
    if joined is None:
        return None
    sums = [decode_expert_hbm_share.attribute_sum(
        facts, {"span": params["span"], "attribute": params[key]},
        joined["window"])[1] for key in ("numerator", "denominator")]
    if not sums[1]:
        return None
    return sums[0] / sums[1] * params.get("scale", 1.0)
