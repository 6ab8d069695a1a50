"""Seconds inside the language model's two programs over request wall (a
``trinity`` cell's)."""

from cdtbench.kinds.trinity import moved


def read(ctx):
    if ctx["cell"].config.get("kind") != "trinity":
        return None
    done = [r for r in ctx["records"] if r["status"] == "success"]
    inside = moved(ctx, "cdt_pipeline_execute_seconds",
                   {"pipeline": "^llm_(prefill|decode)$"}, "sum")
    if not done or inside == 0.0:
        return None
    return 100.0 * inside / sum(r["seconds"] for r in done)
