"""The attention core's SELECTED operations over the compute peak and the
DEVICE time spent under its kernel's name, in percent (a ``glm`` cell's):
``cdtbench/kinds/glm.py: sparse_core_mxu_pct``."""

from cdtbench.kinds.glm import sparse_core_mxu_pct as read  # noqa: F401
