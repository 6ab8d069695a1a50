"""The blocked causal shared-K/V kernel's algorithmic operations over the
compute peak and the DEVICE time spent under its name, in percent."""

import re

from cdtbench.flops import peak_flops
from cdtbench.kinds.jamba import (ATTENTION_KERNEL, attention_core_flops,
                                  request_sizes)


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "jamba" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    seconds = sum(s for name, s in ctx["trace"]["op_seconds"].items()
                  if re.search(ATTENTION_KERNEL, name))
    if not program or not program["count"] or not seconds:
        return None
    need = program["count"] * attention_core_flops(cell.config,
                                                   request_sizes(cell)[0])
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds
