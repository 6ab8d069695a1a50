"""Share of the device's busy seconds under a ``cdt.<layer>`` scope."""

from cdtbench import device_layers


def read(ctx):
    return device_layers.read_named(ctx)
