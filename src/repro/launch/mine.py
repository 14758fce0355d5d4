"""CLI: frequent-itemset mining with the paper's algorithms.

  PYTHONPATH=src python -m repro.launch.mine --dataset mushroom --min-sup 0.3 \
      --algorithm optimized_vfpc [--input file.txt] [--checkpoint-dir ckpt/]
"""

from __future__ import annotations

import argparse
import json

from repro.core import ALGORITHMS, mine
from repro.core.mapreduce import IMPLS
from repro.data import dataset_by_name, load_transactions
from repro.launch.cache import enable_compile_cache
from repro.launch.cliopts import (add_mesh_args, add_obs_args,
                                  add_policy_args, policy_kwargs_from_args,
                                  runtime_from_args, tracer_from_args,
                                  write_obs_outputs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mushroom",
                    help="named synthetic dataset (c20d10k/chess/mushroom/...)")
    ap.add_argument("--input", default=None, help="FIMI-format transaction file")
    ap.add_argument("--min-sup", type=float, default=0.3)
    ap.add_argument("--algorithm", default="optimized_vfpc",
                    choices=sorted(ALGORITHMS))
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--impl", default="auto", choices=("auto", *IMPLS),
                    help="counting impl (auto: pallas on TPU, vertical "
                         "elsewhere)")
    ap.add_argument("--json-out", default=None)
    add_policy_args(ap)
    add_mesh_args(ap)
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    tracer = tracer_from_args(args)

    if args.input:
        txns, n_items = load_transactions(args.input)
    else:
        txns, n_items = dataset_by_name(args.dataset, seed=args.seed,
                                        scale=args.scale)
    runtime, mesh_kwargs = runtime_from_args(
        args, impl=None if args.impl == "auto" else args.impl)
    res = mine(txns, n_items=n_items, min_sup=args.min_sup,
               algorithm=args.algorithm, runtime=runtime,
               policy_kwargs=policy_kwargs_from_args(args, args.algorithm),
               checkpoint_dir=args.checkpoint_dir, **mesh_kwargs)

    print(f"algorithm={res.algorithm} min_sup={res.min_sup} "
          f"n_txns={res.n_txns} n_items={res.n_items}")
    print(f"mesh={runtime.mesh_split[0]}x{runtime.mesh_split[1]} "
          f"(data x cand) impl={runtime.impl} "
          f"repartitions={res.repartitions} retries={res.retries}")
    print(f"phases={res.n_phases} dispatches={res.dispatches} "
          f"compiles={res.compiles} total={res.total_seconds:.2f}s")
    for ph in res.phases:
        ks = f"k={ph.k_start}..{ph.k_start + ph.npass - 1}"
        print(f"  phase {ks:10s} width={ph.npass} cands={ph.candidate_counts} "
              f"freq={ph.frequent_counts} {ph.elapsed_seconds:.3f}s "
              f"(gen {ph.gen_seconds:.3f} count {ph.count_seconds:.3f})")
    sizes = {k: int(v[0].shape[0]) for k, v in sorted(res.levels.items())}
    print("frequent itemsets per level:", sizes)
    if res.decisions:
        print(f"cost-model decisions: {len(res.decisions)} "
              f"(render with `python -m repro.launch.report --decisions`)")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"levels": sizes, "phases": res.n_phases,
                       "total_seconds": res.total_seconds,
                       "dispatches": res.dispatches,
                       "decisions": res.decisions}, f, indent=2)
    write_obs_outputs(args, tracer)


if __name__ == "__main__":
    main()
