"""Seconds of persistent-cache hits when the window opens. A run that
compiled everything reads 0, and says so; only a program without the
set-up ledger leaves the metric out."""

from cdtbench.readers import total
from cdtbench.server import series

BUILD = "cdt_program_build_seconds"


def read(ctx):
    if not series(ctx["opened"], BUILD):
        return None
    return total(ctx["opened"], BUILD, {"phase": "^cache_(key|read)$"},
                 "sum", ctx["cell"])
