"""Placing the data set on the mesh per mine: the program's
``mine.scatter`` spans, in milliseconds."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    found = layer.spans(ctx, "mine.scatter")
    if not mines or not found:
        return None
    return 1e3 * sum(s.duration for s in found) / len(mines)
