"""The layer is in ``ffn_xla_mxu_pct.json``."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_xla_mxu(ctx, "ffn_xla_mxu_pct")
