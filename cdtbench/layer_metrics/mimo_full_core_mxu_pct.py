"""The full layers' causal kernel: its algorithmic operations over the compute
peak and the DEVICE time under its name, in percent (a ``mimo`` cell's):
``cdtbench/kinds/mimo.py: full_core_mxu_pct``."""

from cdtbench.kinds.mimo import full_core_mxu_pct as read  # noqa: F401
