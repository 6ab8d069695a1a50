"""``llm_prefill``'s algorithmic operations over the compute peak and the
program's DEVICE time, in percent (a ``kimi`` cell's)."""

from cdtbench.flops import peak_flops
from cdtbench.kinds.kimi import prefill_flops, request_sizes
from cdtbench.readers import total


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "kimi" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu" or not ctx["requests"]:
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    if not program or not program["count"]:
        return None
    name, match = "cdt_llm_expert_slots_total", {"phase": "^prefill$",
                                                 "where": "^held$"}
    held = (total(ctx["closed"], name, match, "value", cell)
            - total(ctx["opened"], name, match, "value", cell))
    need = prefill_flops(cell.config, request_sizes(cell)[0],
                         held / ctx["requests"])
    seconds = program["seconds"] / program["count"]
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds
