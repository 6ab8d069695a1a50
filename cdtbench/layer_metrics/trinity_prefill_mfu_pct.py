"""``llm_prefill``'s algorithmic operations over the compute peak and the
program's DEVICE time, in percent (a ``trinity`` cell's)."""

from cdtbench.flops import peak_flops
from cdtbench.kinds.trinity import moved, prefill_flops, request_sizes


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "trinity" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu" or not ctx["requests"]:
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    if not program or not program["count"]:
        return None
    held = moved(ctx, "cdt_llm_expert_slots_total",
                 {"phase": "^prefill$", "where": "^held$"})
    need = prefill_flops(cell.config, request_sizes(cell)[0],
                         held / ctx["requests"])
    seconds = program["seconds"] / program["count"]
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds
