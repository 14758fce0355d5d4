"""Shared benchmark plumbing."""

from __future__ import annotations

import json
import os
import time

from repro.core import mine
from repro.core.mapreduce import MapReduceRuntime
from repro.data import dataset_by_name

ALGOS = ["spc", "fpc", "dpc", "vfpc", "etdpc", "optimized_vfpc", "optimized_etdpc"]

# scaled-down stand-ins for the paper's three datasets (CPU-sized); min_sup
# chosen so mining reaches ≥5 levels (the multi-pass regime the paper targets)
DATASETS = {
    "c20d10k": {"scale": 0.10, "min_sup": 0.125},
    "chess": {"scale": 0.10, "min_sup": 0.55},
    "mushroom": {"scale": 0.08, "min_sup": 0.31},
}


def require_cpu_parent(suite: str) -> None:
    """Refuse to run a suite that simulates devices in child processes
    unless this process is on the CPU: a parent on an accelerator holds the
    chip, and CPU children would report host-simulated numbers beside it."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{suite} simulates devices on the CPU in child processes; this "
            f"process is on {backend!r} — run it with JAX_PLATFORMS=cpu")


def load(name: str, scale=None, seed: int = 0):
    return dataset_by_name(name, seed=seed, scale=scale or DATASETS[name]["scale"])


def timed_mine(txns, n_items, min_sup, algorithm, *, reps: int = 1,
               warm: bool = False, runtime: MapReduceRuntime | None = None,
               **kw):
    """Run ``mine`` and time it.

    ``warm=True`` runs once un-timed first (compile caches populated) and then
    reports the best of ``reps`` timed runs on the same runtime — the
    steady-state number used for before/after comparisons.
    """
    runtime = runtime or MapReduceRuntime()
    if warm:
        mine(txns, n_items=n_items, min_sup=min_sup, algorithm=algorithm,
             runtime=runtime, **kw)
    best, res = float("inf"), None
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        res = mine(txns, n_items=n_items, min_sup=min_sup, algorithm=algorithm,
                   runtime=runtime, **kw)
        best = min(best, time.perf_counter() - t0)
    return res, best


def emit(rows, header):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    print()


def write_json(filename: str, payload: dict) -> str:
    """Dump a benchmark record next to the repo root (tracked across PRs)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, filename)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"# wrote {path}")
    return path
