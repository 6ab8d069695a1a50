"""The selective-scan kernel's necessary bytes over the HBM peak and the
DEVICE time spent under its name, in percent."""

import re

from cdtbench.kinds.jamba import SCAN_KERNEL, hbm_peak, scan_bytes
from cdtbench.readers import total


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "jamba" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu" or not ctx["requests"]:
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    seconds = sum(s for name, s in ctx["trace"]["op_seconds"].items()
                  if re.search(SCAN_KERNEL, name))
    name, match = "cdt_llm_scan_tokens_total", {"phase": "^prefill$"}
    walked = (total(ctx["closed"], name, match, "value", cell)
              - total(ctx["opened"], name, match, "value", cell))
    if not program or not program["count"] or not seconds or not walked:
        return None
    need = scan_bytes(cell.config) * program["count"] * walked \
        / ctx["requests"]
    return 100.0 * need / hbm_peak(ctx["device"]["kind"]) / seconds
