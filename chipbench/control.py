"""The control of the comparison that decides ``correct``.

    python3 -m chipbench.control --workload t40.mine --seeds 1 2 3

The control is the reference put in the program's place with its counts in
bfloat16, the precision below the exact integer supports the configurations
state.  For each seed it generates the cell's data at the cell's own size
and prints the number the cell compares (itemsets missing, extra or with
another count than the exact reference's), which must come out above the
limit of 0: ``control <workload> seed=<n> itemset_mismatches=<reading>``.
The benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import sys
import time

from chipbench import quest, reference
from chipbench.modes.mine import level_table, mismatches
from chipbench.run import ROOT, load_cell


def readings(loaded: dict, seed: int) -> dict:
    cfg = loaded["config"]
    n_items = cfg["data"]["n_items"]
    rows = quest.generate(**cfg["data"], seed=seed)
    min_sup = cfg["mining"]["min_sup"]
    exact = reference.mine(rows, n_items, min_sup)
    control = reference.mine(rows, n_items, min_sup, "bfloat16")
    return {"itemset_mismatches": mismatches(level_table(control, n_items),
                                             level_table(exact, n_items))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    loaded = load_cell(ROOT, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        got = readings(loaded, seed)
        print(f"control {args.workload} seed={seed} "
              + " ".join(f"{k}={v}" for k, v in got.items())
              + f" seconds={time.perf_counter() - t:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
