"""The layers and program phases are in ``longcat_moe_branch_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_share(ctx, "longcat_moe_branch_pct")
