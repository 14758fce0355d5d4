"""Shared measurement + persistence layer (DESIGN.md §9).

Every adaptive decision in the stack ultimately rests on *measured elapsed
time* — the paper's ETDPC insight.  This module owns the two primitives the
measurers share so they cannot drift apart:

* :func:`time_once` — the warm-up + best-of-reps timing loop the block
  autotuner (``kernels/autotune.py``) and the cost-model benchmarks use;
* :func:`cache_dir` / :class:`JsonStore` — best-effort JSON persistence in
  the same directory as the autotune cache, so tunings and cost-model fits
  live (and ship) side by side;
* :func:`device_key` — the ``backend:device_kind`` identity that keys both
  caches.  Keying on ``jax.default_backend()`` alone silently reuses one
  machine's timings on another (two different GPUs are both ``"gpu"``); the
  concrete device kind disambiguates.
"""

from __future__ import annotations

import json
import os
import re


def cache_dir() -> str:
    """Directory shared by the autotune cache and the cost-model store."""
    return os.path.join(os.path.expanduser("~"), ".cache", "repro")


def device_key(backend: str | None = None) -> str:
    """``backend:device_kind`` cache identity for the current (or named)
    backend — e.g. ``cpu:cpu``, ``tpu:TPU-v5e``, ``gpu:NVIDIA-H100``."""
    import jax
    backend = backend or jax.default_backend()
    kind = jax.devices(backend)[0].device_kind
    return f"{backend}:{re.sub(r'[^A-Za-z0-9_.]+', '-', kind).strip('-')}"


def time_once(fn, reps: int = 2, clock=None) -> float:
    """Best-of-``reps`` wall time of ``fn()`` after one warm-up call.

    The warm-up run pays compile cost; the timed runs block on the result, so
    the number is steady-state device time + dispatch overhead — exactly what
    the cost model wants to fit and the autotuner wants to rank.

    ``clock`` follows the injectable-clock contract (DESIGN.md §13): any
    object with ``now() -> float`` seconds; default the monotonic wall
    clock.  Tests pass :class:`repro.obs.clock.FakeClock` to script timings.
    """
    import jax
    if clock is None:
        clock = _monotonic_clock()
    out = fn()                      # warm-up: compile + first run
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = clock.now()
        jax.block_until_ready(fn())
        best = min(best, clock.now() - t0)
    return best


def _monotonic_clock():
    from repro.obs.clock import MonotonicClock
    return MonotonicClock()


class JsonStore:
    """Best-effort persisted JSON dict (atomic replace; errors never raise).

    The in-memory dict is authoritative for the process; disk is a warm-start
    for the next one — the same contract as the autotune disk cache.
    """

    def __init__(self, path: str):
        self.path = path

    def load(self) -> dict:
        try:
            with open(self.path) as f:
                out = json.load(f)
            return out if isinstance(out, dict) else {}
        except (OSError, ValueError):
            return {}

    def save(self, store: dict) -> None:
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(store, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass


def costmodel_store() -> JsonStore:
    """The persisted cost-model fit store (override: REPRO_COSTMODEL_CACHE;
    ``REPRO_COSTMODEL_CACHE=""`` disables persistence via a /dev/null-ish
    path that simply fails to write)."""
    env = os.environ.get("REPRO_COSTMODEL_CACHE")
    path = env if env else os.path.join(cache_dir(), "costmodel.json")
    return JsonStore(path)
