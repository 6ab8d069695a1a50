"""Host seconds inside ``llm_decode`` over the tokens the cell's graph asks
of it, in milliseconds a token (a ``jamba`` cell's)."""

from cdtbench.kinds.jamba import request_sizes
from cdtbench.readers import total


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "jamba" or not ctx["requests"]:
        return None
    match = {"pipeline": "^llm_decode$"}
    inside = (total(ctx["closed"], "cdt_pipeline_execute_seconds", match,
                    "sum", cell)
              - total(ctx["opened"], "cdt_pipeline_execute_seconds", match,
                      "sum", cell))
    if inside == 0.0:
        return None
    return 1000.0 * inside / ctx["requests"] / request_sizes(cell)[1]
