"""Host seconds inside ``llm_decode`` a decoded token (a ``mimo`` cell's):
``cdtbench/kinds/mimo.py: decode_ms_per_token``."""

from cdtbench.kinds.mimo import decode_ms_per_token as read  # noqa: F401
