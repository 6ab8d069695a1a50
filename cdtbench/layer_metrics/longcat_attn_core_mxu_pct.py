"""The blocked causal kernel's algorithmic operations over the compute peak
and the DEVICE time spent under its name, in percent (a ``longcat``
cell's): ``cdtbench/kinds/longcat.py: attn_core_mxu_pct``."""

from cdtbench.kinds.longcat import attn_core_mxu_pct as read  # noqa: F401
