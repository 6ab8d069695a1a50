"""Seconds inside the language model's two programs over request wall (a
``glm`` cell's):
``cdtbench/kinds/glm.py: share_pct``."""

from cdtbench.kinds.glm import share_pct as read  # noqa: F401
