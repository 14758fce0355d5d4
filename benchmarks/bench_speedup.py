"""Paper Fig. 5(b): speedup vs number of DataNodes.

Each device count runs in a subprocess with its own
``--xla_force_host_platform_device_count`` (the host-device simulation of a
bigger cluster).  NOTE: on a one-core host the simulated devices
time-share one CPU, so wall-clock speedup is expected to be flat —
the benchmark validates the *harness* (shards scale, answers agree) and
reports per-device work reduction; real scaling numbers need real chips.
"""

import json
import os
import subprocess
import sys

from .common import emit, require_cpu_parent

_CHILD = r"""
import json, time, sys
import numpy as np
from repro.data import dataset_by_name
from repro.core import mine
from repro.core.mapreduce import MapReduceRuntime
txns, n_items = dataset_by_name("c20d10k", scale=0.1)
rt = MapReduceRuntime()
t0 = time.perf_counter()
res = mine(txns, n_items=n_items, min_sup=0.35, algorithm="%s", runtime=rt)
wall = time.perf_counter() - t0
import jax
sizes = {k: int(v[0].shape[0]) for k, v in res.levels.items()}
print(json.dumps({"wall": wall, "devices": len(jax.devices()),
                  "rows_counted": rt.stats.rows_counted,
                  "dispatches": rt.stats.dispatches, "levels": sizes}))
"""


def run(fast: bool = False):
    require_cpu_parent("bench_speedup")
    rows = []
    counts = [1, 4] if fast else [1, 2, 4, 8]
    for algo in ["vfpc", "optimized_vfpc"] if not fast else ["optimized_vfpc"]:
        base = None
        ref_levels = None
        for n in counts:
            env = dict(os.environ)
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
            r = subprocess.run([sys.executable, "-c", _CHILD % algo],
                               capture_output=True, text=True, env=env,
                               timeout=600)
            assert r.returncode == 0, r.stderr
            data = json.loads(r.stdout.strip().splitlines()[-1])
            if ref_levels is None:
                ref_levels = data["levels"]
                base = data["wall"]
            assert data["levels"] == ref_levels, "answers must agree across meshes"
            rows.append((f"fig5b_speedup/{algo}/devices={n}",
                         round(data["wall"] * 1e6 / data["dispatches"], 1),
                         f"wall={data['wall']:.3f}s speedup={base/data['wall']:.2f} "
                         f"dispatches={data['dispatches']}"))
    emit(rows, ["name", "us_per_call", "derived"])
    return rows


if __name__ == "__main__":
    run()
