"""``llm_prefill``'s algorithmic operations over the compute peak and the
program's DEVICE time, in percent (a ``glm`` cell's):
``cdtbench/kinds/glm.py: prefill_mfu_pct``."""

from cdtbench.kinds.glm import prefill_mfu_pct as read  # noqa: F401
