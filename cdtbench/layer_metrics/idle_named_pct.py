"""Share of the chip's idle seconds, inside the traced request, that lies
under a mirrored span saying what the host did (``cdtbench/host_spans.py``)."""

from cdtbench import host_spans
from cdtbench.server import ROOT, say


def read(ctx):
    if ctx.get("trace") is None:
        return None
    out_dir = ROOT / "chiprun_out" / "cdtbench" / ctx["cell"].name
    answer = host_spans.run(out_dir / "profile", out_dir / "host_spans.json",
                            say)
    return None if answer is None else answer["idle_named_pct"]
