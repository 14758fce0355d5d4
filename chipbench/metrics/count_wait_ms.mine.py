"""The host's wait on the count jobs, per mine: the program's
``mine.count.wait`` spans (the block on the device, the fetch of the
results and their unpacking), in milliseconds."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    found = layer.spans(ctx, "mine.count.wait")
    if not mines or not found:
        return None
    return 1e3 * sum(s.duration for s in found) / len(mines)
