"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in a mine cell."""

from chipbench import layer


def read(ctx):
    return layer.idle_share(ctx) if layer.mines(ctx) else None
