"""The exchange's share of the counting programs' device time, in a mine
cell on several chips: the device time of the all-reduce operations
(``chipbench/exchange.py``) over the device time of the counting programs
(``layer.COUNT_MODULE``), both the mean over the chips.  Where the trace
holds no all-reduce, as on one chip, it reads nothing."""

from chipbench import exchange, layer


def read(ctx):
    if not layer.mines(ctx) or ctx.trace is None:
        return None
    count_s = ctx.trace.module_seconds(lambda n: layer.COUNT_MODULE in n)
    reduce_s = ctx.trace.op_seconds(exchange.is_exchange)
    if count_s <= 0 or reduce_s <= 0:
        return None
    return 100.0 * reduce_s / count_s
