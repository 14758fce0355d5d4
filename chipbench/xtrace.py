"""From a JAX profiler trace to device busy, idle and per-program times.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
a plain list of events: ``(plane, line, name, start_ns, dur_ns)``.  Device
planes are named ``/device:TPU:<n>``; on them the line ``XLA Modules``
holds one event per program run and ``XLA Ops`` one per operation.  The
host planes hold the host's annotations (``jax.profiler.TraceAnnotation``)
on the same clock.

:func:`reduce` is the whole reduction, kept with the benchmark so that every
run computes the numbers the same way; it is tested on a small recorded
trace (``testdata/small_trace.json``).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def load(trace_dir: str) -> list[tuple]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def load_json(path: str) -> list[tuple]:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]


def _union_ns(intervals: list[tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _clip(s: int, d: int, lo: int, hi: int):
    a, b = max(s, lo), min(s + d, hi)
    return (a, b) if b > a else None


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: list                       # device plane names, sorted
    busy_s: dict                        # device -> seconds of op union
    module_s: dict                      # device -> {module name: seconds}
    op_s: dict                          # device -> {op name: seconds}
    gaps: list                          # (start_ns, end_ns) idle on device 0
    host: list                          # host annotation events in window

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def module_seconds(self, match) -> float:
        """Seconds per device in modules whose name ``match`` accepts,
        averaged over the devices."""
        tot = sum(s for mods in self.module_s.values()
                  for n, s in mods.items() if match(n))
        return tot / max(len(self.module_s), 1)

    def op_seconds(self, match) -> float:
        tot = sum(s for ops in self.op_s.values()
                  for n, s in ops.items() if match(n))
        return tot / max(len(self.op_s), 1)


def reduce(events: list[tuple], window_ns: tuple[int, int]) -> Reduced:
    """Busy time (union of ops), per-module and per-op seconds, and the
    idle gaps of the first device, all clipped to ``window_ns``."""
    lo, hi = window_ns
    devices = sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)})
    busy, mods, ops, gaps = {}, {}, {}, []
    for dev in devices:
        iv, m, o = [], {}, {}
        for plane, line, name, s, d in events:
            if plane != dev:
                continue
            c = _clip(s, d, lo, hi)
            if c is None:
                continue
            base = name.split("(")[0]
            if line == OP_LINE:
                iv.append(c)
                o[base] = o.get(base, 0.0) + (c[1] - c[0]) / 1e9
            elif line == MODULE_LINE:
                m[base] = m.get(base, 0.0) + (c[1] - c[0]) / 1e9
        busy[dev] = _union_ns(iv) / 1e9
        mods[dev], ops[dev] = m, o
        if dev == devices[0]:
            t = lo
            for s, e in sorted(iv):
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if hi > t:
                gaps.append((t, hi))
    host = [(name, s, d) for plane, line, name, s, d in events
            if not plane.startswith(DEVICE_PREFIX) and _clip(s, d, lo, hi)]
    return Reduced((hi - lo) / 1e9, devices, busy, mods, ops, gaps, host)


def window_of(events: list[tuple], annotation: str) -> tuple[int, int]:
    """``(start_ns, end_ns)`` of the host annotation named ``annotation``."""
    for plane, line, name, s, d in events:
        if name == annotation and not plane.startswith(DEVICE_PREFIX):
            return s, s + d
    raise ValueError(f"annotation {annotation!r} not in the trace")


def top_ops(red: Reduced, n: int = 10) -> list:
    """The device operations that took most time, seconds per device."""
    tot: dict = {}
    for ops in red.op_s.values():
        for name, s in ops.items():
            tot[name] = tot.get(name, 0.0) + s / len(red.op_s)
    return sorted(([k, v] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(red: Reduced, spans: list, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the first device, each named by the
    innermost span in ``spans`` (``(name, start_ns, end_ns)`` on the trace's
    clock) that covers the gap's middle, else ``"host"``."""
    out = []
    for s, e in sorted(red.gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        inner = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner else "host"
        out.append([name, (e - s) / 1e9])
    return out
