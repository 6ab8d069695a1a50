"""Host seconds inside ``llm_decode`` over the tokens the cell's graph asks
of it, in milliseconds a token (a ``sala`` cell's):
``cdtbench/kinds/sala.py: decode_ms_per_token``."""

from cdtbench.kinds.sala import decode_ms_per_token as read  # noqa: F401
