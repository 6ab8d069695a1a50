"""The scope is in ``keye_index_pct.json``; the reader is
``cdtbench/kinds/keye.py: scope_pct``."""

from cdtbench.kinds.keye import scope_pct


def read(ctx):
    return scope_pct(ctx, "llm_index")
