"""The sparse prefill kernel as a share of the chip's bf16 peak, in percent,
over ONE window: the operations the selection REQUIRES of the chunks
enqueued in the traced window over the device time of the kernel and the
chip's published peak (``peaks.json``).

Operations: the program says of each chunk which one it is and how long its
prompt (the attributes ``chunk`` and ``tokens`` of the span ``span``,
``llm.prefill.chunk``); for the chunks whose span ends inside the window,
``chunk_required_attention_flops`` of the module ``bytes`` at the sizes of
the configuration file ``config`` counts q k^T and p v of every real query
over the positions the equations let it attend to (all of them up to
``dense_len``, the chosen blocks past it), summed.  Time: every operation
of ``program`` in the window under one of ``scopes`` whose instruction name
holds one of ``names`` (the kernel's custom call).  The kernel computes a
whole tile of keys for a tile of queries wherever ANY query of the tile
chose a block of it, so it does at least what is required and the share
cannot pass 100; what it does beyond is the tiles' cost.

None where there is no capture, no op map, no such operation or no such
span (the parent of the PR that added them)."""

import importlib
import json

from perfbench import device, manifest, op_scopes, program_trace
from perfbench.reducers import scope_ms_per_span


def reduce(facts: dict, params: dict):
    joined = op_scopes.of_run(facts)
    if joined is None or not joined["events"]:
        return None
    seconds = scope_ms_per_span.selected_seconds(joined, params)
    chunks = chunks_in(facts, params, joined["window"])
    if not seconds or not chunks:
        return None
    import jax
    peak = device.peaks_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    required = importlib.import_module(
        params["bytes"]).chunk_required_attention_flops
    flops = sum(required(config, index * params["chunk"], tokens,
                         params["chunk"]) for index, tokens in chunks)
    if isinstance(facts.get("notes"), dict):
        facts["notes"]["prefill_sparse_peak"] = {
            "chunks": len(chunks), "kernel_seconds": seconds,
            "required_flops": flops}
    return 100.0 * flops / seconds / peak


def chunks_in(facts: dict, params: dict, window) -> list:
    """(chunk index, prompt tokens) of every span named ``span`` that ends
    inside the window, read from the run's capture."""
    path = program_trace.capture_of(facts)
    if path is None:
        return []
    from jax.profiler import ProfileData
    return listed(ProfileData.from_file(path).planes, params, window)


def listed(planes, params: dict, window) -> list:
    start, end = window
    found = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != params["span"]:
                    continue
                stats = dict(ev.stats)
                done = (ev.start_ns + ev.duration_ns) / 1e9
                if start < done <= end and "chunk" in stats \
                        and "tokens" in stats:
                    found.append((int(stats["chunk"]), int(stats["tokens"])))
    return found
