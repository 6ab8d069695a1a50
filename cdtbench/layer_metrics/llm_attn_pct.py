"""The layers and program phases are in ``llm_attn_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_share(ctx, "llm_attn_pct")
