"""A decode step's expert matmuls as a share of the memory roofline, in
percent, over ONE window: the bytes of expert weights the decode steps of
the traced window had to read over the device time their grouped-matmul
kernels took and the chip's published memory bandwidth (``peaks.json``).

Bytes: the program says of each decode step how many distinct experts it
chose, summed over the routed layers (the attribute ``attribute`` of the
span ``span``: ``experts_touched`` on ``llm.decode.pull``, which ends when
that step's ids are on the host; a padded row holds a live row's choice, so
what is counted is what the step read); the steps pulled inside the window
are summed and multiplied by one expert's bytes (the function
``expert_bytes`` of the module ``bytes``, at the sizes of the configuration
file ``config``).  Time: every operation of ``program`` in the window that
its op map puts under one of ``scopes`` and whose instruction name holds
one of ``names`` (``scope_ms_by_name`` says why by name), wherever the host
was: neither side is divided by a count of spans or of steps (PR 37: a step
may be drained outside an ``llm.decode`` span).  A step pulled just inside
the window whose kernels ran before it, and the reverse at the other edge,
are one step in some seven hundred.  Bound by bytes: a kernel reads at
least the experts that were chosen, so the share cannot pass 100.

None where there is no capture, no op map, no such operation or no such
attribute (a program without them: the parent of the PR that added them).
With ``--notes`` the run's notes keep what was divided
(``decode_expert_hbm``)."""

import importlib
import json

from perfbench import device, manifest, op_scopes, program_trace
from perfbench.reducers import scope_ms_by_name


def reduce(facts: dict, params: dict):
    joined = op_scopes.of_run(facts)
    if joined is None or not joined["events"]:
        return None
    seconds = sum(e[3] for e in scope_ms_by_name.named(
        next(iter(joined["events"].values())), params)
        if op_scopes.selects(e, params))
    steps, touched = attribute_sum(facts, params, joined["window"])
    if not seconds or not touched:
        return None
    import jax
    peak = device.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    moved = touched * importlib.import_module(
        params["bytes"]).expert_bytes(config)
    if isinstance(facts.get("notes"), dict):
        facts["notes"]["decode_expert_hbm"] = {
            "steps": steps, "experts_touched": touched,
            "kernel_seconds": seconds, "bytes": moved}
    return 100.0 * moved / seconds / peak


def attribute_sum(facts: dict, params: dict, window) -> tuple:
    """(spans counted, the sum of their attribute): the spans named
    ``span`` that end inside the window and carry ``attribute``, read from
    the run's capture (``program_trace`` keeps a span's name and times
    only)."""
    path = program_trace.capture_of(facts)
    if path is None:
        return 0, 0
    from jax.profiler import ProfileData
    return summed(ProfileData.from_file(path).planes, params, window)


def summed(planes, params: dict, window) -> tuple:
    start, end = window
    count = total = 0
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != params["span"]:
                    continue
                done = (ev.start_ns + ev.duration_ns) / 1e9
                value = dict(ev.stats).get(params["attribute"])
                if start < done <= end and value is not None:
                    count, total = count + 1, total + int(value)
    return count, total
