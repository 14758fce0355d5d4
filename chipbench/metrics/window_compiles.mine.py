"""XLA compiles inside the measured window of a mine cell (JAX's
monitoring events): every shape should be warm, so this reads 0."""

from chipbench import layer


def read(ctx):
    return ctx.compiles_in_window if layer.mines(ctx) else None
