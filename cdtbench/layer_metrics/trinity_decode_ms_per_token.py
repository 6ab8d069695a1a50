"""Host seconds inside ``llm_decode`` over the tokens the cell's graph asks
of it, in milliseconds a token (a ``trinity`` cell's)."""

from cdtbench.kinds.trinity import moved, request_sizes


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "trinity" or not ctx["requests"]:
        return None
    inside = moved(ctx, "cdt_pipeline_execute_seconds",
                   {"pipeline": "^llm_decode$"}, "sum")
    if inside == 0.0:
        return None
    return 1000.0 * inside / ctx["requests"] / request_sizes(cell)[1]
