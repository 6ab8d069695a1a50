"""Seconds inside the language model's two programs over request wall (a
``keye`` cell's):
``cdtbench/kinds/keye.py: share_pct``."""

from cdtbench.kinds.keye import share_pct as read  # noqa: F401
