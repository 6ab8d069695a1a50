"""``llm_prefill``'s algorithmic operations over the compute peak and the
program's DEVICE time, in percent (a ``longcat`` cell's):
``cdtbench/kinds/longcat.py: prefill_mfu_pct``."""

from cdtbench.kinds.longcat import prefill_mfu_pct as read  # noqa: F401
