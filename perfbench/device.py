"""What the run is on: the device as JAX reports it, and its published peaks."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip of this kind.  A kind that is not in
    peaks.json is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}: add it to "
            f"{PEAKS_FILE.name} with its source (have {sorted(table)})")
    return table[device_kind]


def claim_devices(chips: int, rehearse: bool) -> list:
    """The devices this cell runs on.  Without an accelerator, or with
    fewer chips than the cell asks for, the run ends with no result."""
    import jax
    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want:
        raise SystemExit(
            f"perfbench: this cell measures a TPU and JAX found "
            f"{devices[0].platform!r}; a CPU time is not a device metric "
            "(--rehearse runs the control flow at a tiny size)")
    if len(devices) < chips:
        raise SystemExit(
            f"perfbench: the cell asks for {chips} chips and JAX found "
            f"{len(devices)}")
    return devices[:chips]


def describe(devices: list) -> dict:
    """The ``device`` object of the result line.  ``memory_peak_bytes`` is
    the fullest chip's ``peak_bytes_in_use``: the arrays the process held
    (weights, optimizer state, the KV pool's device copy).  On the v5e's
    runtime it leaves out a program's temporaries, which the runtime
    counts under ``peak_bytes_reserved``; ``--notes`` records both."""
    import jax
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}
