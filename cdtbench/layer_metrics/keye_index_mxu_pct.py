"""The index-score kernel's operations over the compute peak and the DEVICE
time spent under its name, in percent (a ``keye`` cell's):
``cdtbench/kinds/keye.py: index_mxu_pct``."""

from cdtbench.kinds.keye import index_mxu_pct as read  # noqa: F401
