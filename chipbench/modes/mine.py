"""Back-to-back exact mines of the whole data set.

Set-up generates the configuration's data set from the seed, builds one
``MapReduceRuntime`` on the cell's mesh with the counting form and block
tuning the configuration pins, and runs one full mine, which compiles every
program the window runs.  The
window then calls ``repro.core.mine`` on the same runtime, one mine after
another, until ``seconds`` have passed; it ends when the last mine ends.
``mine_s`` is the window over the mines completed.

Every mine of the window is compared, level by level, itemset by itemset
and count by count, with the reference's mine of the same rows.
"""

from __future__ import annotations

import json
import time

import numpy as np

from chipbench import quest, reference


def data_rows(ctx, seed: int) -> np.ndarray:
    return quest.generate(**ctx.config["data"], seed=seed)


def setup(ctx, seed: int) -> dict:
    from repro.core import mine
    from repro.core.mapreduce import MapReduceRuntime
    from repro.launch.mesh import make_mining_mesh

    masks = data_rows(ctx, seed)
    m = dict(ctx.config["mining"])
    mesh = make_mining_mesh(ctx.chips, 1)
    runtime = MapReduceRuntime(mesh=mesh, impl=m.pop("impl"),
                               autotune=m.pop("autotune"))
    # every other key of the configuration's "mining" is a mine() argument
    kwargs = dict(db_masks=masks, n_items=ctx.config["data"]["n_items"],
                  runtime=runtime, **m)
    warm = mine(**kwargs)
    return {"mine": mine, "kwargs": kwargs, "runtime": runtime,
            "masks": masks, "warm": warm}


def window(ctx, state: dict, seconds: float) -> dict:
    mine, kwargs = state["mine"], state["kwargs"]
    results, times = [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        t = time.perf_counter()
        results.append(mine(**kwargs))
        times.append(time.perf_counter() - t)
        if time.perf_counter() >= deadline:
            break
    window_s = time.perf_counter() - t0
    ctx.window.update(mines=results, mine_seconds=times, window_s=window_s)
    return {"mine_s": window_s / len(results)}


def plan(res) -> str:
    return " ".join(
        f"k{p.k_start}+{p.npass}:c={'/'.join(map(str, p.candidate_counts))}"
        f":f={'/'.join(map(str, p.frequent_counts))}" for p in res.phases)


def describe(ctx, state: dict) -> None:
    """Pass plans, counting form, blocks and mesh, so that a change of plan
    or form between runs is told apart from a change of speed."""
    from repro.kernels.autotune import DEFAULTS
    rt = state["runtime"]
    plans: dict = {}
    for res in [state["warm"], *ctx.window["mines"]]:
        plans[plan(res)] = plans.get(plan(res), 0) + 1
    for p, n in plans.items():
        print(f"plan mines={n} {p}", flush=True)
    blocks = "tuned" if rt.autotune else json.dumps(DEFAULTS.get(rt.impl))
    print(f"form impl={rt.impl} blocks={blocks} "
          f"mesh={rt.mesh_split[0]}x{rt.mesh_split[1]} "
          f"devices={rt.mesh.size} mines={len(ctx.window['mines'])} "
          f"window_s={ctx.window['window_s']:.6f}", flush=True)


def level_table(levels: dict, n_items: int) -> dict:
    """``{k: {packed mask bytes: count}}`` of program or reference levels."""
    out = {}
    for k, (a, counts) in levels.items():
        a = np.asarray(a)
        if a.shape[0] == 0:
            continue
        if a.dtype != np.uint32:          # reference: item ids
            a = reference.to_masks(a, n_items)
        out[k] = {r.tobytes(): int(c) for r, c in zip(a, counts)}
    return out


def mismatches(got: dict, want: dict) -> int:
    """Itemsets missing, extra, or with another count."""
    n = 0
    for k in set(got) | set(want):
        g, w = got.get(k, {}), want.get(k, {})
        n += len(g.keys() ^ w.keys())
        n += sum(g[key] != w[key] for key in g.keys() & w.keys())
    return n


def check(ctx, state: dict, log) -> tuple[int, int, dict]:
    n_items = ctx.config["data"]["n_items"]
    t = time.perf_counter()
    want = level_table(reference.mine(state["masks"], n_items,
                                      ctx.config["mining"]["min_sup"]),
                       n_items)
    bad = [mismatches(level_table(r.levels, n_items), want)
           for r in ctx.window["mines"]]
    log(f"reference levels={'/'.join(str(len(want[k])) for k in sorted(want))}"
        f" seconds={time.perf_counter() - t:.3f} mines_checked={len(bad)}")
    checks = {"itemset_mismatches": {"value": sum(bad), "limit": 0}}
    return len(bad), sum(b > 0 for b in bad), checks
