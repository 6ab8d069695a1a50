"""The layers and program phases are in ``sampler_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_share(ctx, "sampler_pct")
