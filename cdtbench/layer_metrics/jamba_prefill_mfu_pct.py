"""``llm_prefill``'s algorithmic matrix operations over the compute peak
and the program's DEVICE time, in percent (a ``jamba`` cell's)."""

from cdtbench.flops import peak_flops
from cdtbench.kinds.jamba import prefill_flops, request_sizes


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "jamba" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    if not program or not program["count"]:
        return None
    need = prefill_flops(cell.config, request_sizes(cell)[0])
    seconds = program["seconds"] / program["count"]
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds
