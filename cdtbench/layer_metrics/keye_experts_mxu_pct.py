"""The routed slots' operations over the compute peak and the DEVICE seconds
under ``cdt.llm_experts`` in prefill, in percent (a ``keye`` cell's):
``cdtbench/kinds/keye.py: experts_mxu_pct``."""

from cdtbench.kinds.keye import experts_mxu_pct as read  # noqa: F401
