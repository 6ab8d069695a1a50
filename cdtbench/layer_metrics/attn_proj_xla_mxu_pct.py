"""The layer is in ``attn_proj_xla_mxu_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_xla_mxu(ctx, "attn_proj_xla_mxu_pct")
