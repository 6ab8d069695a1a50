"""Host seconds inside ``llm_decode`` a decoded token (a ``glm`` cell's):
``cdtbench/kinds/glm.py: decode_ms_per_token``."""

from cdtbench.kinds.glm import decode_ms_per_token as read  # noqa: F401
