"""Bytes a decoded token must move over the HBM peak and the DEVICE time a
token, in percent (a ``sala`` cell's):
``cdtbench/kinds/sala.py: decode_hbm_pct``."""

from cdtbench.kinds.sala import decode_hbm_pct as read  # noqa: F401
