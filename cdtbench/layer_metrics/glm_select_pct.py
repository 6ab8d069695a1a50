"""The scope is in ``glm_select_pct.json``; the reader is
``cdtbench/kinds/glm.py: scope_pct``."""

from cdtbench.kinds.glm import scope_pct


def read(ctx):
    return scope_pct(ctx, "llm_select")
