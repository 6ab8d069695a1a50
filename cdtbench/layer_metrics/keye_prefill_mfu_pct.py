"""``llm_prefill``'s algorithmic operations over the compute peak and the
program's DEVICE time, in percent (a ``keye`` cell's):
``cdtbench/kinds/keye.py: prefill_mfu_pct``."""

from cdtbench.kinds.keye import prefill_mfu_pct as read  # noqa: F401
