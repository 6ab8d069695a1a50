"""The state form's two products over the compute peak and the DEVICE seconds
under ``llm_retention`` in the prefill, in percent (a ``brumby`` cell's):
``cdtbench/kinds/brumby.py: retention_mxu_pct``."""

from cdtbench.kinds.brumby import retention_mxu_pct as read  # noqa: F401
