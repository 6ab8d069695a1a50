"""Bytes a decoded token must read over the HBM peak and the DEVICE time
a token, in percent: ``llm_decode``'s share of its memory roofline in a
``trinity`` cell."""

from cdtbench.kinds.trinity import (decode_bytes_per_token, hbm_peak, moved,
                                    request_sizes)


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "trinity" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_decode")
    series = "cdt_llm_expert_slots_total"
    slots = moved(ctx, series, {"phase": "^decode$"})
    if not program or not program["count"] or not slots:
        return None
    held_share = moved(ctx, series, {"phase": "^decode$",
                                     "where": "^held$"}) / slots
    prompt_tokens, new_tokens = request_sizes(cell)
    token_s = program["seconds"] / program["count"] / new_tokens
    need = decode_bytes_per_token(cell.config, held_share, prompt_tokens,
                                  new_tokens)
    return 100.0 * need / hbm_peak(ctx["device"]["kind"]) / token_s
