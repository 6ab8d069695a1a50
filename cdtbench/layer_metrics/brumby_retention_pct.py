"""The share of the two language programs' DEVICE seconds under the plain
named scope ``llm_retention`` (a ``brumby`` cell's):
``cdtbench/kinds/brumby.py: retention_pct``."""

from cdtbench.kinds.brumby import retention_pct as read  # noqa: F401
