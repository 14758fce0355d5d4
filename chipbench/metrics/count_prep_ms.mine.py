"""The host side of the count jobs before their dispatch, per mine: the
program's ``mine.count.prep`` spans (joining and padding the levels,
building the candidate payload and placing it on the mesh), in
milliseconds."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    found = layer.spans(ctx, "mine.count.prep")
    if not mines or not found:
        return None
    return 1e3 * sum(s.duration for s in found) / len(mines)
