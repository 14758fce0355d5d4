"""Helpers the per-layer metric readers share.

Each reader in ``chipbench/metrics/<name>.py`` has one function,
``read(ctx)``, that returns the metric's value or ``None`` where the run
holds nothing for it to read (another kind of cell, or an untraced run).
"""

from __future__ import annotations

COUNT_MODULE = "mapper"        # the shard_map'd counting job of the runtime


def mines(ctx):
    return ctx.window.get("mines") or None


def spans(ctx, name: str) -> list:
    return [s for s in ctx.spans if s.name == name]


def self_seconds(ctx, name: str) -> float:
    """Summed self time of the spans called ``name``: each span's duration
    less the union of the other spans inside it."""
    total = 0.0
    for s in spans(ctx, name):
        inner = sorted((c.t0, c.t1) for c in ctx.spans
                       if c is not s and s.t0 <= c.t0 and c.t1 <= s.t1)
        covered, end = 0.0, s.t0
        for a, b in inner:
            a = max(a, end)
            if b > a:
                covered += b - a
                end = b
        total += s.duration - covered
    return total


def idle_share(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_s / ctx.trace.window_s)
