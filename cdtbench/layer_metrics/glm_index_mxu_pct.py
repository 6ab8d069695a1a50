"""The index-score kernel's operations over the compute peak and the DEVICE
time spent under its name, in percent (a ``glm`` cell's):
``cdtbench/kinds/glm.py: index_mxu_pct``."""

from cdtbench.kinds.glm import index_mxu_pct as read  # noqa: F401
