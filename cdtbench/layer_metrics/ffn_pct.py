"""The layers and program phases are in ``ffn_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_share(ctx, "ffn_pct")
