"""Apriori pruning inside candidate generation, per mine: the
``prune_seconds`` the program records on its ``mine.gen`` spans, in
milliseconds.  It is part of ``gen_ms.mine``, not taken from it."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    found = [s.attrs["prune_seconds"] for s in layer.spans(ctx, "mine.gen")
             if "prune_seconds" in s.attrs]
    if not mines or not found:
        return None
    return 1e3 * sum(found) / len(mines)
