"""A Ling sequence's row of KDA state as the unit that
``reducers/decode_pages_hbm_share.py`` multiplies a span's count by: the
reducer asks its ``bytes`` module for ``page_bytes``, and here the "page"
is one sequence's row over the KDA layers held, read and written once a
decode step (``perfbench/bytes_ling.py``).  The count is the attribute
``state_rows`` of the program's ``llm.decode`` span: the LIVE rows of the
step it enqueued, not the bucket's 64 (the in-place kernels step a row
that names none without moving it, so counting the bucket would count
bytes that nobody moved, and the share could pass 100)."""

from __future__ import annotations

from perfbench import bytes_ling


def page_bytes(sizes: dict) -> int:
    """One live row's state, read and written."""
    return bytes_ling.decode_state_bytes(sizes, 1)
