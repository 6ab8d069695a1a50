"""Host seconds inside ``llm_decode`` a decoded token (a ``zaya`` cell's):
``cdtbench/kinds/zaya.py: decode_ms_per_token``."""

from cdtbench.kinds.zaya import decode_ms_per_token as read  # noqa: F401
