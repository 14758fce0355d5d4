"""The counting jobs' share of their roofline.

The least time of the window's counting jobs (``chipbench/work.py``: the
compulsory bytes over the HBM bandwidth of the chips used) over the device
time of the counting programs in the trace, averaged over the chips.  Every
counting form gets the same work, so a change of form moves the share only
by moving the time."""

from chipbench import layer, work


def _items_touched(phase, n_items: int) -> int:
    if phase.k_start == 1:
        return n_items                    # every item is a 1-candidate
    seen = set()
    for masks, _ in phase.levels.values():
        for row in masks:
            for w, word in enumerate(row):
                word = int(word)
                while word:
                    low = word & -word
                    seen.add(32 * w + low.bit_length() - 1)
                    word ^= low
    return len(seen)


def read(ctx):
    mines = layer.mines(ctx)
    if not mines or ctx.trace is None:
        return None
    device_s = ctx.trace.module_seconds(lambda n: layer.COUNT_MODULE in n)
    if device_s <= 0:
        return None
    bw = ctx.peaks["hbm_bytes_per_s"]
    least = 0.0
    for res in mines:
        for p in res.phases:
            least += work.least_seconds(
                sum(p.candidate_counts), res.n_txns,
                _items_touched(p, res.n_items), bw, ctx.chips)
    return 100.0 * least / device_s
