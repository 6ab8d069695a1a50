"""The grouped-query kernel of the window layers: its algorithmic
operations over the compute peak and the DEVICE time under its name, in
percent (``cdtbench/kinds/trinity.py``: ``core_mxu_pct``)."""

from cdtbench.kinds.trinity import core_mxu_pct


def read(ctx):
    return core_mxu_pct(ctx, "window")
