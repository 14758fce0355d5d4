"""Building the data set's per-shard vertical bitmaps on the host per mine:
the ``pack_seconds`` the program records on its ``mine.scatter`` spans, in
milliseconds.  It is part of ``scatter_ms.mine``, not taken from it."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    found = [s.attrs["pack_seconds"] for s in layer.spans(ctx, "mine.scatter")
             if "pack_seconds" in s.attrs]
    if not mines or not found:
        return None
    return 1e3 * sum(found) / len(mines)
