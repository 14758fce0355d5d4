"""A small copy of the benchmark for CPU tests of the harness.

``make_tiny`` copies ``chipbench/`` and ``BENCHMARK.json`` to a directory,
shrinks each configuration to a few thousand rows, pins the jnp counting
form and links the program's ``src``, so that ``chipbench.run.run`` drives
a whole run in seconds with ``require_tpu=False``.
"""

import json
import os
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

TINY = {"quest-t40i10d100k": (3000, 100, 0.05),
        "quest-t10i4d100k": (4000, 64, 0.04)}


def make_tiny(dest: Path) -> Path:
    shutil.copytree(REPO / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    os.symlink(REPO / "src", dest / "src")
    for name, (rows, items, sup) in TINY.items():
        p = dest / "chipbench" / "configs" / f"{name}.json"
        c = json.loads(p.read_text())
        c["data"].update(n_txns=rows, n_items=items, avg_width=8,
                         n_patterns=20, avg_pattern_len=4)
        c["mining"].update(min_sup=sup, impl="jnp")
        p.write_text(json.dumps(c))
    return dest


def run_tiny(root: Path, workload: str, trace: bool = False, seed: int = 5,
             seconds: float = 0.3) -> dict:
    from chipbench import run
    return run.run(workload, seed, seconds, trace, bench_root=root,
                   require_tpu=False, log=lambda *a: None)
