"""Bytes a decoded token must read over the HBM peak and the DEVICE time a
token, in percent (a ``glm`` cell's):
``cdtbench/kinds/glm.py: decode_hbm_pct``."""

from cdtbench.kinds.glm import decode_hbm_pct as read  # noqa: F401
