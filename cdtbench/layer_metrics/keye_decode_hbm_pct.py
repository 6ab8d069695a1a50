"""Bytes a decoded token must read over the HBM peak and the DEVICE time a
token, in percent (a ``keye`` cell's):
``cdtbench/kinds/keye.py: decode_hbm_pct``."""

from cdtbench.kinds.keye import decode_hbm_pct as read  # noqa: F401
