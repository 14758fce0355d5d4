"""The count job's exchange across chips: which device operations are it,
and the least time it can take.

Each count job ends in one ``psum`` over the mesh's ``data`` axis (the
shuffle and reduce of ``repro.core.mapreduce``).  XLA compiles it for the
v5e to one ``all-reduce`` instruction that keeps the primitive's name: the
four-chip trace calls the level-2 job's ``%psum.7 = s32[417792]{0:T(1024)}
all-reduce(...)`` (``testdata/mesh4_trace.json``).  The trace reduction
keeps an op's name up to its first ``(``, so an op is the exchange when
that part names the psum or an all-reduce.

A bandwidth-optimal all-reduce of ``B`` bytes on each of ``d`` chips sends
at least ``2·(d-1)/d·B`` bytes from every chip (a reduce-scatter, then an
all-gather), so at a chip's interconnect peak it takes no less than that
over the peak.  No schedule of the exchange beats it, so a share of it
cannot pass 1 unless the trace misses op time.
"""

from __future__ import annotations

NAMES = ("all-reduce", "psum")


def is_exchange(op_name: str) -> bool:
    """True for a device operation of the count job's exchange."""
    name = op_name.lower()
    return any(n in name for n in NAMES)


def least_seconds(exchange_bytes: float, chips: int,
                  ici_bits_per_s: float) -> float:
    """The least time of an all-reduce into which ``chips`` chips put
    ``exchange_bytes`` in all; 0 on one chip, where nothing moves."""
    if chips < 2:
        return 0.0
    per_chip = exchange_bytes / chips
    return 2.0 * (chips - 1) / chips * per_chip / (ici_bits_per_s / 8.0)
