"""The scope is in ``sala_lightning_pct.json``; the reader is
``cdtbench/kinds/sala.py: scope_pct``."""

from cdtbench.kinds.sala import scope_pct


def read(ctx):
    return scope_pct(ctx, "lightning")
