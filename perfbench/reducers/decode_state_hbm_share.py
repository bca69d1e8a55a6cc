"""The decode step's update of the recurrent state as a share of the
memory roofline, in percent: the bytes one step has to read and write
(``perfbench/bytes_falcon_h1.py`` at the sizes of the configuration file
``config`` and the rows of its one decode bucket) over the device time of
the operations that touch the store in one step (``ops_ms_in_span`` with
the same ``names``, ``shapes`` and ``span``) and the chip's published
memory bandwidth (``peaks.json``).  Bound by bytes: the update does two
operations a byte."""

import json

from perfbench import bytes_falcon_h1, device, manifest
from perfbench.families import falcon_h1
from perfbench.reducers import ops_ms_in_span


def reduce(facts: dict, params: dict):
    ms = ops_ms_in_span.reduce(facts, params)
    if not ms:
        return None
    import jax
    peak = device.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    config = json.loads((manifest.ROOT / params["config"]).read_text())
    rows = max(config["serve"]["engine"]["decode_batch_buckets"])
    moved = bytes_falcon_h1.decode_state_bytes(falcon_h1.sizes(config), rows)
    return 100.0 * moved / (ms * 1e-3) / peak
