"""``decode_pages_hbm_share``'s unit for Keye's score pass: one position's
index key in one layer (``bytes_keye.index_key_bytes``: 64 float32 lanes,
256 B), counted by the attribute ``positions_scored`` of
``llm.decode.pull``."""

from perfbench.bytes_keye import index_key_bytes as page_bytes  # noqa: F401
