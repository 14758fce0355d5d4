"""Strong/weak scaling curves vs. device count (DESIGN.md §11).

The paper's Fig. 5 varies the Hadoop cluster size; here the cluster is a
device mesh, simulated on one host via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.  That flag is fixed
at process start, so each device count runs in a fresh worker subprocess
(``python -m benchmarks.bench_scaling --worker <cfg>``) that mines once and
reports a JSON record; the parent sweeps the counts and writes
``BENCH_scaling.json``.

Arms:

* **strong** — fixed dataset, growing mesh: wall time per device count and
  speedup vs. 1 device.  On a single physical CPU the simulated devices add
  no parallel compute, so the honest win is cache locality: per-shard
  vertical bitmaps fit cache at transaction counts where the monolithic
  layout does not (large-scale c20d10k, vertical impl).
* **weak** — dataset grows with the mesh (scale ∝ devices): per-transaction
  time should stay flat when sharding is efficient.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit, require_cpu_parent, write_json

_MARK = "@@SCALING@@ "

# full-mode arms: the large-T regime where per-shard cache residency wins
STRONG = {"dataset": "c20d10k", "scale": 64.0, "min_sup": 0.25,
          "impl": "vertical", "algorithm": "optimized_etdpc"}
WEAK = {"dataset": "c20d10k", "scale_per_device": 4.0, "min_sup": 0.25,
        "impl": "vertical", "algorithm": "optimized_etdpc"}
DEVICES = [1, 2, 4, 8]

SMOKE_STRONG = {"dataset": "c20d10k", "scale": 0.5, "min_sup": 0.25,
                "impl": "vertical", "algorithm": "optimized_etdpc"}
SMOKE_WEAK = {"dataset": "c20d10k", "scale_per_device": 0.1, "min_sup": 0.25,
              "impl": "vertical", "algorithm": "optimized_etdpc"}
SMOKE_DEVICES = [1, 8]


def _worker(cfg: dict) -> None:
    """Mine once at the current (already-forced) device count; print JSON."""
    from repro.core.mapreduce import MapReduceRuntime
    from repro.launch.mesh import make_mining_mesh

    from .common import load, timed_mine

    txns, n_items = load(cfg["dataset"], scale=cfg["scale"])
    runtime = MapReduceRuntime(mesh=make_mining_mesh(n_cand=cfg["n_cand"]),
                               impl=cfg["impl"],
                               cand_axis="cand" if cfg["n_cand"] > 1 else None)
    res, wall = timed_mine(txns, n_items, cfg["min_sup"], cfg["algorithm"],
                           warm=True, runtime=runtime, elastic=False)
    print(_MARK + json.dumps({
        "devices": runtime.mesh.size, "mesh": list(runtime.mesh_split),
        "n_txns": len(txns), "wall": wall, "phases": res.n_phases,
        "dispatches": res.dispatches,
        "levels": {str(k): int(v[0].shape[0]) for k, v in res.levels.items()},
    }))


def _spawn(cfg: dict, n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scaling",
         "--worker", json.dumps(cfg)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"scaling worker failed (devices={n_devices}): "
        f"{proc.stderr.strip().splitlines()[-1] if proc.stderr else '?'}")


def run(fast: bool = False):
    require_cpu_parent("bench_scaling")
    strong = dict(SMOKE_STRONG if fast else STRONG, n_cand=1)
    weak = dict(SMOKE_WEAK if fast else WEAK, n_cand=1)
    devices = SMOKE_DEVICES if fast else DEVICES

    rows = []
    strong_arms = []
    for n in devices:
        rec = _spawn(strong, n)
        strong_arms.append(rec)
        rows.append((f"scaling_strong/{strong['dataset']}/devices={n}",
                     round(rec["wall"] * 1e6 / rec["n_txns"], 3),
                     f"wall={rec['wall']:.3f}s mesh={rec['mesh']}"))

    weak_arms = []
    for n in devices:
        cfg = dict(weak, scale=weak["scale_per_device"] * n)
        rec = _spawn(cfg, n)
        weak_arms.append(rec)
        rows.append((f"scaling_weak/{weak['dataset']}/devices={n}",
                     round(rec["wall"] * 1e6 / rec["n_txns"], 3),
                     f"wall={rec['wall']:.3f}s n={rec['n_txns']}"))

    payload = {"mode": "smoke" if fast else "full",
               "strong": dict(strong, arms=strong_arms),
               "weak": dict(weak, arms=weak_arms)}
    by_dev = {a["devices"]: a["wall"] for a in strong_arms}
    if 1 in by_dev and max(by_dev) > 1:
        top = max(by_dev)
        payload["strong"]["speedup"] = {
            str(d): round(by_dev[1] / w, 4) for d, w in sorted(by_dev.items())}
        rows.append((f"scaling_strong/speedup_{top}x", 0,
                     f"{by_dev[1] / by_dev[top]:.3f}x vs 1 device"))
    if weak_arms:
        per_txn = {a["devices"]: a["wall"] / a["n_txns"] for a in weak_arms}
        base = per_txn[min(per_txn)]
        payload["weak"]["efficiency"] = {
            str(d): round(base / t, 4) for d, t in sorted(per_txn.items())}
    write_json("BENCH_scaling.json", payload)
    emit(rows, ["name", "us_per_call", "derived"])
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--worker", default=None, help="internal: JSON config")
    args = ap.parse_args()
    if args.worker:
        _worker(json.loads(args.worker))
    else:
        run(fast=args.smoke)
