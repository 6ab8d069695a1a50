"""Attended pairs over causal pairs, in percent (a ``glm`` cell's):
``cdtbench/kinds/glm.py: selected_keys_pct``."""

from cdtbench.kinds.glm import selected_keys_pct as read  # noqa: F401
