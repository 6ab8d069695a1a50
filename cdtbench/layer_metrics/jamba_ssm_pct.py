"""The layers and program phases are in ``jamba_ssm_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_share(ctx, "jamba_ssm_pct")
