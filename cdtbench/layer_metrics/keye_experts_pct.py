"""The expert layer's share of the language programs' DEVICE seconds (a
``keye`` cell's):
``cdtbench/kinds/keye.py: experts_pct``."""

from cdtbench.kinds.keye import experts_pct as read  # noqa: F401
