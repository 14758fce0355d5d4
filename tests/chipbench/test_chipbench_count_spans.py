"""The readers of the count job's host side, of pruning, of the bytes
placed on the devices and of the device idle time no step accounts for."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import layer, run, xtrace
from tinybench import run_tiny

TESTDATA = Path(__file__).resolve().parents[2] / "chipbench" / "testdata"
METRICS = Path(__file__).resolve().parents[2] / "chipbench" / "metrics"


def _reader(name: str):
    return run.load_module(METRICS / f"{name}.py")


def _reduced(trace: str):
    events = xtrace.load_json(str(TESTDATA / trace))
    return xtrace.reduce(events, xtrace.window_of(events, "chipbench.window"))


def test_idle_unattributed_on_a_recorded_trace():
    red = _reduced("host_gaps_trace.json")
    assert red.gaps == [(1000000, 1001000), (1002000, 1005000),
                        (1006000, 1009000), (1009500, 1010000)]
    # gap 1: under mine.run alone, 1000 ns.  gap 2: mine.gen and
    # mine.count.prep cover 2000-4500, the container mine.count alone the
    # last 500.  gap 3: wait and spec_join overlap, their union covers
    # 6000-7500 of it.  gap 4: under mine.run alone, 500 ns.
    ctx = SimpleNamespace(window={"mines": [object(), object()]}, trace=red)
    got = _reader("idle_unattributed_ms.mine").read(ctx)
    assert got == pytest.approx((1000 + 500 + 1500 + 500) / 1e6 / 2)


@pytest.mark.parametrize("case", ["no_mine_annotation", "no_device_plane",
                                  "untraced"])
def test_idle_unattributed_reads_nothing_it_cannot_see(case):
    red = _reduced("small_trace.json" if case == "no_mine_annotation"
                   else "host_gaps_trace.json")
    if case == "no_device_plane":
        red.devices, red.gaps = [], []
    ctx = SimpleNamespace(window={"mines": [object()]},
                          trace=None if case == "untraced" else red)
    assert _reader("idle_unattributed_ms.mine").read(ctx) is None


def test_traced_tiny_run_reports_the_count_job_metrics(tiny_bench):
    out = run_tiny(tiny_bench, "t40.mine", trace=True)
    assert out["correct"] is True
    got = out["metrics"]
    for name in ("count_prep_ms.mine", "count_wait_ms.mine",
                 "prune_ms.mine", "h2d_bytes.mine"):
        assert got[name]["value"] > 0, name
    assert got["h2d_bytes.mine"]["unit"] == "bytes"
    # pruning is recorded on mine.gen, inside generation, not beside it
    assert got["prune_ms.mine"]["value"] <= got["gen_ms.mine"]["value"]
    # the CPU has no device plane, so the trace's idle time stays silent
    assert "idle_unattributed_ms.mine" not in got


def test_count_program_name_holds_count_module():
    """A jitted count job's module is named by form and mode, and the name
    still holds what ``count_roofline.mine`` matches on."""
    import numpy as np

    from repro.core.bitset import pack_itemsets, singleton_masks
    from repro.core.mapreduce import MapReduceRuntime
    from repro.core.phases import bucket_pad
    rng = np.random.default_rng(0)
    db = pack_itemsets([rng.choice(20, 5, replace=False).tolist()
                        for _ in range(64)], 20)
    rt = MapReduceRuntime(impl="vertical", autotune=False)
    placed = rt.scatter_db(db, n_items=20)
    payload = rt.place_candidates(bucket_pad(singleton_masks(20)))
    rt.dispatch_count(placed, payload).result()
    (job,) = rt._jitted.values()
    module = job.lower(placed, payload).as_text().split("\n")[0]
    assert "@jit_mapper_vertical_plain " in module
    assert layer.COUNT_MODULE in module
