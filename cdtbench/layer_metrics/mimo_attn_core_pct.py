"""The share of device busy time under the attention cores' scopes (a
``mimo`` cell's): ``cdtbench/kinds/mimo.py: core_pct``."""

from cdtbench.kinds.mimo import core_pct


def read(ctx):
    return core_pct(ctx, ("full", "window"))
