"""The prefill's algorithmic operations over the compute peak and its DEVICE
time, in percent (a ``brumby`` cell's):
``cdtbench/kinds/brumby.py: prefill_mfu_pct``."""

from cdtbench.kinds.brumby import prefill_mfu_pct as read  # noqa: F401
