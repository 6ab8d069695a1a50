"""The prefill's algorithmic operations over the compute peak and its DEVICE
time, in percent (a ``zaya`` cell's): ``cdtbench/kinds/zaya.py:
prefill_mfu_pct``."""

from cdtbench.kinds.zaya import prefill_mfu_pct as read  # noqa: F401
