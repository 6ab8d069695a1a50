"""Attended pairs over causal pairs, in percent (a ``keye`` cell's):
``cdtbench/kinds/keye.py: selected_keys_pct``."""

from cdtbench.kinds.keye import selected_keys_pct as read  # noqa: F401
