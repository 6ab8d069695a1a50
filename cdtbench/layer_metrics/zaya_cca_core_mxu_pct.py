"""The causal kernel's operations at 8 q / 2 kv over the compute peak and its
DEVICE seconds, in percent (a ``zaya`` cell's): ``cdtbench/kinds/zaya.py:
cca_core_mxu_pct``."""

from cdtbench.kinds.zaya import cca_core_mxu_pct as read  # noqa: F401
