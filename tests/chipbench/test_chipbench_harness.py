"""The harness driven end to end on the CPU at a tiny size.

Each run skips only the look for a chip (``require_tpu=False``).  Sound runs
come out correct; runs with the timed path broken underneath, once for each
fault a cell can have, come out not correct.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tinybench import REPO, run_tiny


@pytest.mark.parametrize("workload,e2e", [
    ("t40.mine", {"setup_s", "mine_s"}),
    ("t10.mine", {"setup_s", "mine_s"}),
])
def test_sound_run_is_correct(tiny_bench, workload, e2e):
    out = run_tiny(tiny_bench, workload)
    assert out["correct"] is True
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in out["check"].values())


def test_traced_run_reports_per_layer_metrics(tiny_bench):
    out = run_tiny(tiny_bench, "t10.mine", trace=True)
    assert out["correct"] is True
    got = set(out["metrics"])
    # the CPU has no device plane: the device-trace metrics stay silent
    assert {"window_compiles.mine", "gen_ms.mine", "cand_per_frequent.mine",
            "scatter_ms.mine"} <= got
    assert not got & {"count_roofline.mine", "idle_share.mine"}
    assert "window_s" in out["device"] and "breakdown" in out


def test_new_cell_config_mix_and_metric_are_found_by_name(tiny_bench):
    """Files added next to the others, and entries added to BENCHMARK.json,
    make a new cell and metric; no existing file is edited."""
    root = tiny_bench
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    cfg = json.loads(
        (root / "chipbench/configs/quest-t10i4d100k.json").read_text())
    cfg["mining"]["min_sup"] = 0.2
    (root / "chipbench/configs/t10-sup20.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/mine_once_more.json").write_text(
        json.dumps({"mode": "mine", "loop": "closed", "clients": 1}))
    (root / "chipbench/metrics/levels_found.mine.py").write_text(
        "def read(ctx):\n"
        "    mines = ctx.window.get('mines')\n"
        "    return len(mines[0].levels) if mines else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "t10-sup20", "source": "test",
                             "file": "chipbench/configs/t10-sup20.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "t10s.mine", "config": "t10-sup20",
                               "traffic": "mine_once_more", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][1]["workloads"].append("t10s.mine")
    bench["per_layer"].append({"name": "levels_found.mine", "unit": "levels",
                               "better": "higher", "source": "program_counter",
                               "layer": "drivers", "moves": "mine_s",
                               "workloads": ["t10s.mine"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    plain = run_tiny(root, "t10s.mine")
    traced = run_tiny(root, "t10s.mine", trace=True)
    assert plain["correct"] and traced["correct"]
    assert "mine_s" in plain["metrics"]
    assert traced["metrics"]["levels_found.mine"]["value"] >= 1
    assert "gen_ms.mine" not in traced["metrics"]
    for p, data in before.items():
        assert p.read_bytes() == data, p


# -- faults: each must turn `correct` false --------------------------------

def _alter_counts(monkeypatch):
    from repro.core import mapreduce
    orig = mapreduce.CountFuture.result

    def result(self):
        out = orig(self)
        if isinstance(out, tuple):            # fused job: (keep, counts)
            keep, counts = out
            counts = counts.copy()
            counts[np.flatnonzero(keep)[:1]] += 1
            return keep, counts
        # plain job: every row's count, the all-zero padding rows at the
        # top (every transaction holds the empty set); alter the largest
        # count below theirs
        counts = out.copy()
        counts[np.argmax(np.where(counts < counts.max(), counts, -1))] += 1
        return counts
    monkeypatch.setattr(mapreduce.CountFuture, "result", result)


def _half_rows(monkeypatch):
    from repro.core import mapreduce
    orig = mapreduce.MapReduceRuntime.scatter_db

    def scatter_db(self, db_masks, n_items=None):
        return orig(self, db_masks[:db_masks.shape[0] // 2], n_items)
    monkeypatch.setattr(mapreduce.MapReduceRuntime, "scatter_db", scatter_db)


def _phase_returns_nothing(monkeypatch):
    from repro.core import drivers
    from repro.core.phases import PhaseResult

    def run_phase(runtime, db_sharded, n_txns, prev_frequent, k_prev, *a,
                  **kw):
        return PhaseResult(k_prev + 1, 0, [], 0.0, 0.0, 0.0, [], {}, False)
    monkeypatch.setattr(drivers, "run_phase", run_phase)


@pytest.mark.parametrize("workload,fault", [
    ("t40.mine", _alter_counts),
    ("t40.mine", _half_rows),
    ("t10.mine", _phase_returns_nothing),
    ("t10.mine", _alter_counts),
], ids=lambda v: getattr(v, "__name__", v))
def test_fault_is_not_correct(tiny_bench, monkeypatch, workload, fault):
    fault(monkeypatch)
    out = run_tiny(tiny_bench, workload)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["check"].values())


# -- no chip, no result ------------------------------------------------------

def _cli(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_AUTOTUNE_CACHE=str(tmp_path / "a.json"),
               REPRO_COSTMODEL_CACHE=str(tmp_path / "c.json"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "t10.mine",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu(tmp_path):
    p = _cli(REPO, tmp_path)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    import shutil
    root = tmp_path / "only"
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for d in bench["paths"]:
        shutil.copytree(REPO / d, root / d,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    p = _cli(root, tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
