"""Seconds inside the language model's two programs over request wall (a
``sala`` cell's):
``cdtbench/kinds/sala.py: share_pct``."""

from cdtbench.kinds.sala import share_pct as read  # noqa: F401
