"""Host candidate generation per mine: self time of the program's
``mine.gen`` spans, in milliseconds."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    if not mines or not layer.spans(ctx, "mine.gen"):
        return None
    return 1e3 * layer.self_seconds(ctx, "mine.gen") / len(mines)
