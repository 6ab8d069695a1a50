"""Rows the prefill's expert form multiplied a routed slot (a ``keye``
cell's):
``cdtbench/kinds/keye.py: expert_rows_per_slot``."""

from cdtbench.kinds.keye import expert_rows_per_slot as read  # noqa: F401
