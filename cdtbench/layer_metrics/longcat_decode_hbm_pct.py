"""Bytes a decoded token must read over the HBM peak and the DEVICE time a
token, in percent (a ``longcat`` cell's):
``cdtbench/kinds/longcat.py: decode_hbm_pct``."""

from cdtbench.kinds.longcat import decode_hbm_pct as read  # noqa: F401
