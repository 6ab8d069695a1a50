"""``llm_prefill``'s algorithmic matrix operations over the compute peak and
the program's DEVICE time, in percent (a ``sala`` cell's):
``cdtbench/kinds/sala.py: prefill_mfu_pct``."""

from cdtbench.kinds.sala import prefill_mfu_pct as read  # noqa: F401
