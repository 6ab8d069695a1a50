"""Host seconds inside ``llm_decode`` a decoded token (a ``keye`` cell's):
``cdtbench/kinds/keye.py: decode_ms_per_token``."""

from cdtbench.kinds.keye import decode_ms_per_token as read  # noqa: F401
