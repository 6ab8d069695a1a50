"""The sparse attention kernel's SELECTED operations over the compute peak and
the DEVICE time spent under its name, in percent (a ``sala`` cell's):
``cdtbench/kinds/sala.py: sparse_core_mxu_pct``."""

from cdtbench.kinds.sala import sparse_core_mxu_pct as read  # noqa: F401
