"""Seconds inside the language model's two programs over request wall (a
``mimo`` cell's):
``cdtbench/kinds/mimo.py: share_pct``."""

from cdtbench.kinds.mimo import share_pct as read  # noqa: F401
