"""Bytes a decoded token must read over the HBM peak and the DEVICE time
a token, in percent: ``llm_decode``'s share of its memory roofline in a
``kimi`` cell."""

from cdtbench.flops import PEAKS
from cdtbench.kinds.kimi import decode_bytes_per_token, request_sizes
from cdtbench.readers import total


def _moved(ctx, match):
    name, cell = "cdt_llm_expert_slots_total", ctx["cell"]
    return (total(ctx["closed"], name, match, "value", cell)
            - total(ctx["opened"], name, match, "value", cell))


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "kimi" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_decode")
    slots = _moved(ctx, {"phase": "^decode$"})
    if not program or not program["count"] or not slots:
        return None
    held_share = _moved(ctx, {"phase": "^decode$", "where": "^held$"}) / slots
    bandwidth = next((peak[1] for kind, peak in PEAKS.items()
                      if kind.lower() in ctx["device"]["kind"].lower()), None)
    if bandwidth is None:
        raise ValueError(f"no peak on record for {ctx['device']['kind']!r}")
    prompt_tokens, new_tokens = request_sizes(cell)
    token_s = program["seconds"] / program["count"] / new_tokens
    need = decode_bytes_per_token(cell.config, held_share, prompt_tokens,
                                  new_tokens)
    return 100.0 * need / bandwidth / token_s
