"""Device-resident phase pipeline: fused-vs-unfused counting, async futures,
speculative join, and the block autotuner (cross-form equivalence against the
oracle lives in test_mine_forms.py)."""

import json
import os

import numpy as np
import pytest

from minedata import planted_baskets
from repro.core import mine
from repro.core.bitset import pack_itemsets
from repro.core.candidates import join_pairs, speculative_join
from repro.core.mapreduce import MapReduceRuntime
from repro.core.phases import bucket_pad


def _levels_snapshot(res):
    return {k: (v[0].copy(), v[1].copy()) for k, v in sorted(res.levels.items())}


def _assert_levels_equal(a, b, ctx):
    assert a.keys() == b.keys(), ctx
    for k in a:
        np.testing.assert_array_equal(a[k][0], b[k][0],
                                      err_msg=f"{ctx}: masks at k={k}")
        np.testing.assert_array_equal(a[k][1], b[k][1],
                                      err_msg=f"{ctx}: counts at k={k}")


@pytest.mark.parametrize("algo", ["spc", "vfpc", "optimized_vfpc",
                                  "optimized_etdpc"])
def test_fused_matches_unfused(algo):
    """The fused (device-filter) path and the legacy unfused path agree."""
    txns, n_items = planted_baskets(seed=3)
    rt_f = MapReduceRuntime(autotune=False)
    res_f = mine(txns, n_items=n_items, min_sup=0.3, algorithm=algo,
                 runtime=rt_f, pipeline=True)
    rt_u = MapReduceRuntime(autotune=False)
    res_u = mine(txns, n_items=n_items, min_sup=0.3, algorithm=algo,
                 runtime=rt_u, pipeline=False)
    _assert_levels_equal(_levels_snapshot(res_f), _levels_snapshot(res_u), algo)
    assert rt_f.stats.fused_dispatches == rt_f.stats.dispatches
    assert rt_u.stats.fused_dispatches == 0
    # fused jobs move strictly fewer result bytes to the host
    assert rt_f.stats.bytes_to_host < rt_u.stats.bytes_to_host


def test_phase_count_filtered_matches_phase_count():
    """Runtime-level: fused keep mask == host-side threshold on plain counts."""
    txns, n_items = planted_baskets(seed=7)
    db = pack_itemsets(txns, n_items)
    rt = MapReduceRuntime(autotune=False)
    sharded = rt.scatter_db(db, n_items=n_items)
    rng = np.random.default_rng(0)
    cands = bucket_pad(db[rng.integers(0, len(db), 100)])
    min_count = 0.25 * len(txns)
    counts = rt.phase_count(sharded, cands)
    keep, fcounts = rt.phase_count_filtered(sharded, cands, min_count)
    np.testing.assert_array_equal(keep, counts >= min_count)
    np.testing.assert_array_equal(fcounts[keep], counts[keep])
    assert (fcounts[~keep] == 0).all()
    # mask-only transfer drops the counts payload entirely
    keep2, nothing = rt.phase_count_filtered(sharded, cands, min_count,
                                             with_counts=False)
    np.testing.assert_array_equal(keep2, keep)
    assert nothing is None


def test_count_future_is_async_handle():
    txns, n_items = planted_baskets(seed=11)
    db = pack_itemsets(txns, n_items)
    rt = MapReduceRuntime(autotune=False)
    sharded = rt.scatter_db(db, n_items=n_items)
    cands = bucket_pad(db[:64])
    fut = rt.phase_count_async(sharded, cands)
    first = fut.result()
    assert first.dtype == np.int64 and first.shape[0] == cands.shape[0]
    assert fut.ready()
    assert fut.result() is first          # result is cached, not re-fetched


def test_speculative_join_resolves_exactly():
    """Pair-filtering the speculative join reproduces join(L) byte-for-byte."""
    rng = np.random.default_rng(2)
    sets_ = {tuple(sorted(rng.choice(30, 3, replace=False))) for _ in range(300)}
    cands = pack_itemsets([list(s) for s in sets_], 30)
    keep = rng.random(cands.shape[0]) < 0.6
    spec = speculative_join(cands, 3)
    want = join_pairs(cands[keep], 3)[0]
    np.testing.assert_array_equal(spec.resolve(keep), want)


def test_join_methods_identical():
    rng = np.random.default_rng(4)
    sets_ = {tuple(sorted(rng.choice(40, 4, replace=False))) for _ in range(500)}
    masks = pack_itemsets([list(s) for s in sets_], 40)
    a, al, ar = join_pairs(masks, 4, method="prefix")
    b, bl, br = join_pairs(masks, 4, method="pairwise")
    np.testing.assert_array_equal(a, b)
    pa = {frozenset((int(x), int(y))) for x, y in zip(al, ar)}
    pb = {frozenset((int(x), int(y))) for x, y in zip(bl, br)}
    assert pa == pb


def test_autotuner_caches_in_process_and_on_disk(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    import repro.kernels.autotune as at
    monkeypatch.setattr(at, "_memory_cache", {})
    cfg = at.tuned_blocks("vertical", C=300, T=200, W=1, kmax=3)
    assert cfg in at.CONFIGS["vertical"]
    disk = json.load(open(tmp_path / "autotune.json"))
    assert len(disk) == 1 and list(disk.values())[0] == cfg
    # second call: in-process hit (and disk content untouched)
    assert at.tuned_blocks("vertical", C=300, T=200, W=1, kmax=3) == cfg
    # interpret impls and REPRO_AUTOTUNE=0 return static defaults untimed
    assert at.tuned_blocks("vertical_pallas_interpret", C=300, T=200) == \
        at.DEFAULTS["vertical_pallas_interpret"]
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert at.tuned_blocks("vertical", C=9999, T=9999) == at.DEFAULTS["vertical"]


def test_overlap_stat_accumulates_when_speculating():
    """A run that speculates records the phase's spec time; overlap_seconds
    only grows when a job was genuinely in flight (never negative)."""
    txns, n_items = planted_baskets(seed=5, n=150)
    rt = MapReduceRuntime(autotune=False)
    res = mine(txns, n_items=n_items, min_sup=0.25,
               algorithm="optimized_vfpc", runtime=rt, pipeline=True)
    assert rt.stats.overlap_seconds >= 0.0
    assert res.overlap_seconds == rt.stats.overlap_seconds
    assert any(p.spec_seconds > 0 for p in res.phases) or \
        rt.stats.overlap_seconds == 0.0
