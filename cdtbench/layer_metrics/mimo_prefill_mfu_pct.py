"""The prefill's algorithmic operations over the compute peak and its DEVICE
time, in percent (a ``mimo`` cell's):
``cdtbench/kinds/mimo.py: prefill_mfu_pct``."""

from cdtbench.kinds.mimo import prefill_mfu_pct as read  # noqa: F401
