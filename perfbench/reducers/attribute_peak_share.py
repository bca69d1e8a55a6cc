"""A scope's kernels as a share of the chip's peak, in percent, over ONE
window: the operations the spans of the traced window say their work
requires over the device time of the scope's operations and the chip's
published bf16 peak (``peaks.json``).

Operations: the program says of each span how many units of work it holds
(the attribute ``attribute`` of the span ``span``: ``positions_read`` on
``llm.prefill.chunk``, the pairs of a real query and a chosen key, summed
over layers); the spans that end inside the window are summed
(``decode_expert_hbm_share.attribute_sum``) and multiplied by one unit's
operations (``unit_flops`` of the module ``flops`` at the sizes of the
configuration file ``config``).  Time: every operation of ``program`` in the
window under one of ``scopes`` whose instruction name holds one of
``names`` (where given), wherever the host was.  A chunk enqueued just
inside the window whose kernels ran before it, and the reverse at the other
edge, are one chunk in some dozens.

None where there is no capture, no op map, no such operation or no such
attribute (the parent of the PR that added them)."""

import importlib
import json

from perfbench import device, manifest, op_scopes
from perfbench.reducers import decode_expert_hbm_share, scope_ms_per_span


def reduce(facts: dict, params: dict):
    joined = op_scopes.of_run(facts)
    if joined is None or not joined["events"]:
        return None
    seconds = scope_ms_per_span.selected_seconds(joined, params)
    spans, units = decode_expert_hbm_share.attribute_sum(
        facts, params, joined["window"])
    if not seconds or not units:
        return None
    import jax
    peak = device.peaks_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    needed = units * importlib.import_module(
        params["flops"]).unit_flops(config)
    if isinstance(facts.get("notes"), dict):
        facts["notes"]["attribute_peak"] = {
            "spans": spans, "units": units, "kernel_seconds": seconds,
            "operations": needed}
    return 100.0 * needed / seconds / peak
