"""The attention core's SELECTED operations over the compute peak and the
DEVICE time spent under its kernel's name, in percent (a ``keye`` cell's):
``cdtbench/kinds/keye.py: sparse_core_mxu_pct``."""

from cdtbench.kinds.keye import sparse_core_mxu_pct as read  # noqa: F401
