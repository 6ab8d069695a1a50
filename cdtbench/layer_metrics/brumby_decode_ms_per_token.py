"""Host seconds inside ``llm_decode`` a decoded token (a ``brumby`` cell's):
``cdtbench/kinds/brumby.py: decode_ms_per_token``."""

from cdtbench.kinds.brumby import decode_ms_per_token as read  # noqa: F401
