"""Seconds inside the language model's two programs over request wall (a
``jamba`` cell's)."""

from cdtbench.readers import total


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "jamba":
        return None
    done = [r for r in ctx["records"] if r["status"] == "success"]
    match = {"pipeline": "^llm_(prefill|decode)$"}
    inside = (total(ctx["closed"], "cdt_pipeline_execute_seconds", match,
                    "sum", cell)
              - total(ctx["opened"], "cdt_pipeline_execute_seconds", match,
                      "sum", cell))
    if not done or inside == 0.0:
        return None
    return 100.0 * inside / sum(r["seconds"] for r in done)
