"""Seconds inside the language model's two programs over request wall (a
``longcat`` cell's): ``cdtbench/kinds/longcat.py: share_pct``."""

from cdtbench.kinds.longcat import share_pct as read  # noqa: F401
