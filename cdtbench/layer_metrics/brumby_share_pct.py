"""Seconds inside the language model's two programs over request wall (a
``brumby`` cell's):
``cdtbench/kinds/brumby.py: share_pct``."""

from cdtbench.kinds.brumby import share_pct as read  # noqa: F401
