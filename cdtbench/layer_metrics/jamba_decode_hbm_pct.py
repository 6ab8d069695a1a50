"""Bytes a decoded token must move over the HBM peak and the DEVICE time a
token, in percent: ``llm_decode``'s share of its memory roofline in a
``jamba`` cell."""

from cdtbench.kinds.jamba import (decode_bytes_per_token, hbm_peak,
                                  request_sizes)


def read(ctx):
    cell = ctx["cell"]
    if cell.config.get("kind") != "jamba" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_decode")
    if not program or not program["count"]:
        return None
    prompt_tokens, new_tokens = request_sizes(cell)
    token_s = program["seconds"] / program["count"] / new_tokens
    need = decode_bytes_per_token(cell.config, prompt_tokens, new_tokens)
    return 100.0 * need / hbm_peak(ctx["device"]["kind"]) / token_s
