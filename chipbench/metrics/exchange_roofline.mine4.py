"""The exchange's share of its roofline, in a mine cell on several chips.

The least time of the window's exchanges (``chipbench/exchange.py``: a
bandwidth-optimal all-reduce of each mine's ``MiningResult.exchange_bytes``
at the per-chip interconnect peak) over the device time of the all-reduce
operations in the trace, the mean over the chips.  Where the trace holds no
all-reduce, or the program counts no exchange, it reads nothing."""

from chipbench import exchange, layer


def read(ctx):
    mines = layer.mines(ctx)
    if not mines or ctx.trace is None:
        return None
    found = [getattr(r, "exchange_bytes", None) for r in mines]
    reduce_s = ctx.trace.op_seconds(exchange.is_exchange)
    if None in found or not sum(found) or reduce_s <= 0:
        return None
    ici = ctx.peaks["ici_bits_per_s"]
    least = sum(exchange.least_seconds(b, ctx.chips, ici) for b in found)
    return 100.0 * least / reduce_s
