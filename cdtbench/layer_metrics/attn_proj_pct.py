"""The layers and program phases are in ``attn_proj_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_share(ctx, "attn_proj_pct")
