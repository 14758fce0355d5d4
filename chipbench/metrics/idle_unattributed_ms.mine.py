"""Device idle time that no step of the mine accounts for, per mine, in
milliseconds: the idle gaps of the first device during which no leaf
``mine.*`` annotation was open on the host.  The containers ``mine.run``,
``mine.phase`` and ``mine.count`` are not leaves.  It reads the trace
alone, so the host's steps and the device's gaps are on one clock; where
the trace holds no ``mine.*`` annotation it reads nothing."""

import bisect

from chipbench import layer

CONTAINERS = {"mine.run", "mine.phase", "mine.count"}


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def unattributed_ns(gaps: list, host: list) -> int | None:
    """Nanoseconds of ``gaps`` (``(start, end)``) outside every leaf
    ``mine.*`` event of ``host`` (``(name, start, duration)``); ``None``
    when ``host`` holds no ``mine.*`` event."""
    mine = [(n, s, d) for n, s, d in host if n.startswith("mine.")]
    if not mine:
        return None
    leaves = _union([(s, s + d) for n, s, d in mine if n not in CONTAINERS])
    starts = [a for a, _ in leaves]
    total = 0
    for g0, g1 in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(leaves) and leaves[i][0] < g1:
            a, b = leaves[i]
            covered += max(0, min(b, g1) - max(a, g0))
            i += 1
        total += (g1 - g0) - covered
    return total


def read(ctx):
    mines = layer.mines(ctx)
    if not mines or ctx.trace is None or not ctx.trace.devices:
        return None
    ns = unattributed_ns(ctx.trace.gaps, ctx.trace.host)
    return None if ns is None else ns / 1e6 / len(mines)
