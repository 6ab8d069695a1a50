"""Bytes a decoded token must move over the HBM peak and the DEVICE time a
token, in percent (a ``brumby`` cell's):
``cdtbench/kinds/brumby.py: decode_hbm_pct``."""

from cdtbench.kinds.brumby import decode_hbm_pct as read  # noqa: F401
