"""Bytes a decoded token must read over the HBM peak and the DEVICE time a
token, in percent (a ``mimo`` cell's):
``cdtbench/kinds/mimo.py: decode_hbm_pct``."""

from cdtbench.kinds.mimo import decode_hbm_pct as read  # noqa: F401
