"""The routed slots' operations over the compute peak and the DEVICE seconds
under ``cdt.llm_experts`` in prefill, in percent (a ``zaya`` cell's):
``cdtbench/kinds/zaya.py: experts_mxu_pct``."""

from cdtbench.kinds.zaya import experts_mxu_pct as read  # noqa: F401
