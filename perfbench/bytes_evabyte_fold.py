"""``decode_pages_hbm_share``'s unit for EvaByte's fold: ONE folded position's
share of a window's fold in one layer (``bytes_evabyte.fold_bytes`` over the
window's 2,048 positions: its own K and V read, 32,768 B, and a sixteenth of
a folded row written, 2,048 B), counted by the attribute ``rows_folded`` of
the span that enqueued the fold (2,048 a window x layers)."""

from perfbench import bytes_evabyte


def page_bytes(sizes: dict) -> float:
    return bytes_evabyte.fold_bytes(sizes) / sizes["window_size"]
