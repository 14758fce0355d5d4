"""Bytes the host placed on the devices per mine, the mean of
``MiningResult.bytes_to_device`` over the window's mines: the data set's
scatter and every count job's candidate payload."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    if not mines:
        return None
    found = [getattr(r, "bytes_to_device", None) for r in mines]
    if None in found:
        return None
    return sum(found) / len(found)
