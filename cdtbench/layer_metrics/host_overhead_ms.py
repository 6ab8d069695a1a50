"""Client wall per request minus the timed programs' seconds per request."""

from cdtbench.readers import total


def read(ctx):
    done = [r for r in ctx["records"] if r["status"] == "success"]
    if not done:
        return None
    in_programs = (
        total(ctx["closed"], "cdt_pipeline_execute_seconds", None, "sum",
              ctx["cell"])
        - total(ctx["opened"], "cdt_pipeline_execute_seconds", None, "sum",
                ctx["cell"]))
    wall = sum(r["seconds"] for r in done)
    return 1000.0 * (wall - in_programs) / len(done)
