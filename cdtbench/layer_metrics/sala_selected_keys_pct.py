"""The sparse layers' attended pairs over the causal pairs, in percent (a
``sala`` cell's):
``cdtbench/kinds/sala.py: selected_keys_pct``."""

from cdtbench.kinds.sala import selected_keys_pct as read  # noqa: F401
