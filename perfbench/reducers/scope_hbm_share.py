"""A scope's device time as a share of the memory roofline, in percent:
the bytes one span's worth of it has to move (the function ``function`` of
the module ``bytes`` at the sizes of the configuration file ``config`` and
the rows of its one decode bucket) over ``scope_ms_per_span`` with the same
``program``, ``scopes`` and ``span``, and the chip's published memory
bandwidth (``peaks.json``).  For a scope bound by bytes.  None where that
reducer reads nothing."""

import importlib
import json

from perfbench import device, manifest
from perfbench.reducers import scope_ms_per_span


def reduce(facts: dict, params: dict):
    ms = scope_ms_per_span.reduce(facts, params)
    if not ms:
        return None
    import jax
    peak = device.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    rows = max(config["serve"]["engine"]["decode_batch_buckets"])
    moved = getattr(importlib.import_module(params["bytes"]),
                    params["function"])(config, rows)
    return 100.0 * moved / (ms * 1e-3) / peak
