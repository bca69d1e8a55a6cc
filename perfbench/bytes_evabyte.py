"""Bytes an EvaByte step has to read, from the configuration's sizes
(config.json names) and from the mathematics of its layer
(``perfbench/EVABYTE.md``), whatever implements it.

A row a sequence HOLDS, exact or folded, is a K and a V of
``num_key_value_heads x head_dim`` float32 lanes in one layer: 2 x 32 x 128 x
4 = 32,768 B.  A decode step's attention has to read every row its live rows
hold: ``rows_read x 32,768`` bytes, where ``rows_read`` is the span's count
(``128 W + r`` a live row x layers, from the step's own lengths, on
``llm.decode.pull``: ``reducers/decode_pages_hbm_share.py``, whose unit, a
"page", is here ONE held row: :func:`page_bytes`).

A slot's worst is just before its 13th window closes at ``max_context``
26,624: 12 closed windows at 2 pages of 64 rows and the open window's 32,
56 pages (:func:`pages_at_most`).

The fold of one window in one layer reads its 2,048 rows and writes 128:
``(2,048 + 128) x 32,768`` bytes (``bytes_evabyte_fold.py`` names a FOLDED
POSITION's share of it as the same reducer's unit)."""

from __future__ import annotations

POOL_ITEMSIZE = 4           # float32 pools
WEIGHT_ITEMSIZE = 2         # bf16, as the configuration's `assumed` says


def head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def row_bytes(sizes: dict) -> int:
    """One held row's K and V in one layer, every KV head."""
    return 2 * sizes["num_key_value_heads"] * head_dim(sizes) * POOL_ITEMSIZE


def page_bytes(sizes: dict) -> int:
    """``decode_pages_hbm_share``'s unit for the walk: one held row."""
    return row_bytes(sizes)


def folded_rows(sizes: dict) -> int:
    """The rows a closed window is kept as."""
    return sizes["window_size"] // sizes["chunk_size"]


def held_rows(sizes: dict, seen: int) -> int:
    """The rows a sequence that has seen ``seen`` positions holds."""
    window = sizes["window_size"]
    return seen // window * folded_rows(sizes) + seen % window


def pages_at_most(sizes: dict, seen: int, page: int) -> int:
    """The most pages of ``page`` rows a sequence holds at once on its way
    to ``seen`` positions: with the last window it fills still exact (just
    before that window closes), or at its end."""
    window, per = sizes["window_size"], folded_rows(sizes) // page
    full = seen // window
    return max((full - 1) * per + window // page if full else 0,
               -(-held_rows(sizes, seen) // page))


def fold_bytes(sizes: dict) -> int:
    """One window's fold in one layer: its rows read, the folded rows
    written."""
    return (sizes["window_size"] + folded_rows(sizes)) * row_bytes(sizes)


def layer_weight_bytes(sizes: dict) -> int:
    """One layer's matrices in the serving type (the norms and phi, mu
    beside them are 0.01%)."""
    e, f = sizes["hidden_size"], sizes["intermediate_size"]
    return (4 * e * e + 3 * e * f) * WEIGHT_ITEMSIZE
