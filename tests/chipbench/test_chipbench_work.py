"""The counting work of ``count_roofline.mine``: one count for every
counting form, and never more than a form must move, so no share of it can
pass 1 at the published peaks."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import peaks, quest, work

REPO = Path(__file__).resolve().parents[2]


def _reader():
    path = REPO / "chipbench" / "metrics" / "count_roofline.mine.py"
    spec = importlib.util.spec_from_file_location("count_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_impls():
    from repro.core.mapreduce import IMPLS
    return [i for i in IMPLS
            if not (i.endswith("pallas") or "pallas_" in i)
            or i.endswith("_interpret")]


@pytest.fixture(scope="module")
def mines_by_impl():
    """One mine of the same small data set under every counting form that
    runs on the CPU."""
    from repro.core import mine
    from repro.core.mapreduce import MapReduceRuntime
    m = quest.generate(n_txns=600, n_items=40, avg_width=6, n_patterns=10,
                       avg_pattern_len=4, correlation=0.5, corruption_mean=0.5,
                       corruption_var=0.1, data_seed=0, seed=1)
    out = {}
    for impl in _cpu_impls():
        out[impl] = mine(db_masks=m, n_items=40, min_sup=0.1,
                         algorithm="optimized_vfpc",
                         runtime=MapReduceRuntime(impl=impl, autotune=False))
    return out


def _ctx(mines, device_s, chips=1):
    trace = SimpleNamespace(
        module_seconds=lambda match: device_s if match("jit_mapper") else 0)
    return SimpleNamespace(window={"mines": mines}, trace=trace, chips=chips,
                           peaks=peaks.peaks("TPU v5 lite"))


def test_every_form_gets_the_same_work(mines_by_impl):
    reader = _reader()
    shares = {impl: reader.read(_ctx([res], 1e-3))
              for impl, res in mines_by_impl.items()}
    assert len(shares) >= 6
    assert len(set(shares.values())) == 1, shares
    assert next(iter(shares.values())) > 0


def test_least_time_is_the_share_denominator(mines_by_impl):
    reader = _reader()
    res = next(iter(mines_by_impl.values()))
    least = 0.0
    for p in res.phases:
        least += work.least_seconds(sum(p.candidate_counts), res.n_txns,
                                    reader._items_touched(p, res.n_items),
                                    819e9)
    # a device time equal to the least time reads exactly 100%
    assert reader.read(_ctx([res], least)) == pytest.approx(100.0)
    assert reader.read(_ctx([res], 2 * least)) == pytest.approx(50.0)
    assert reader.read(_ctx([res], least, chips=4)) == pytest.approx(25.0)


def _form_bytes(impl, C, T, W, n_items, kmax):
    """Bytes a form must stream by its own access pattern."""
    if impl.startswith("vertical"):
        # the item columns it ANDs: at least the kmax rows of each candidate
        return min(n_items + 1, C * kmax) * T / 8 + 4 * C
    return T * W * 4 + 4 * C       # every packed row read once


@pytest.mark.parametrize("C,T,n_items,kmax", [
    (1000, 100_000, 1000, 1), (467_061, 100_000, 1000, 2),
    (60_000, 200_000, 192, 3), (1, 200_000, 192, 6), (256, 1024, 64, 4)])
def test_no_form_can_beat_the_least_time(C, T, n_items, kmax):
    from repro.core.mapreduce import IMPLS
    W = -(-n_items // 32)
    # the items a job touches: at least one candidate's, at most the
    # items its candidates name together
    for touched in (kmax, min(C * kmax, n_items)):
        need = work.count_bytes(C, T, touched)
        for impl in IMPLS:
            assert need <= _form_bytes(impl, C, T, W, n_items, kmax), \
                (impl, C, T, touched)


def test_items_touched_is_a_lower_bound(mines_by_impl):
    """Counted from the frequent itemsets a phase found, which are among
    its candidates."""
    reader = _reader()
    res = mines_by_impl["jnp"]
    for p in res.phases:
        got = reader._items_touched(p, res.n_items)
        if p.k_start == 1:
            assert got == res.n_items
            continue
        items = set()
        for masks, _ in p.levels.values():
            for row in np.asarray(masks):
                bits = np.unpackbits(row.view(np.uint8), bitorder="little")
                items |= set(np.flatnonzero(bits).tolist())
        assert got == len(items)


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
