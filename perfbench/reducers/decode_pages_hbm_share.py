"""A decode step's sparse attention as a share of the memory roofline, in
percent, over ONE window: the bytes of K and V pages the decode steps of the
traced window chose to read over the device time of the paged kernel and
the chip's published memory bandwidth (``peaks.json``).

Bytes: the program says of each decode step how many pages its sparse
layers read, summed over live rows, sparse layers and KV heads (the
attribute ``attribute`` of the span ``span``: ``sparse_pages_read`` on
``llm.decode.pull``: the counts the kernel walked, measured, never the
expectation); the steps pulled inside the window are summed and multiplied
by one page's bytes for one KV head (``page_bytes`` of the module ``bytes``
at the sizes of the configuration file ``config``).  Time: every operation
of ``program`` in the window under one of ``scopes`` whose instruction name
holds one of ``names`` (the kernel's custom call), wherever the host was.
Bound by bytes (a page's position does 4 x 16 operations a byte of K): the
kernel reads at least the pages it was given, so the share cannot pass 100.

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
    steps, pages = decode_expert_hbm_share.attribute_sum(
        facts, params, joined["window"])
    if not seconds or not pages:
        return None
    import jax
    peak = device.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    moved = pages * importlib.import_module(params["bytes"]).page_bytes(config)
    if isinstance(facts.get("notes"), dict):
        facts["notes"]["decode_pages_hbm"] = {
            "steps": steps, "pages_read": pages, "kernel_seconds": seconds,
            "bytes": moved}
    return 100.0 * moved / seconds / peak
