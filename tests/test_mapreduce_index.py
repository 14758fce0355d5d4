"""The vertical count forms' item index: ``MapReduceRuntime._padded_indices``
turns ``(C, W)`` candidate masks into ``(C, kmax)`` item ids, ascending in
each row and padded with the sentinel row ``n_items``.  Checked against a
brute-force reference that unpacks every row's bits."""

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.core.bitset import pack_itemsets
from repro.core.mapreduce import MapReduceRuntime
from repro.core.phases import bucket_pad

N_ITEMS = {1: 32, 2: 64, 32: 1000}      # W -> catalog, the last word's top


def reference_indices(masks: np.ndarray, sentinel: int) -> np.ndarray:
    """Row by row: the set bits of the unpacked row, in order, then the
    sentinel up to the widest row (at least one column)."""
    rows = [np.flatnonzero(np.unpackbits(
        np.ascontiguousarray(r, dtype="<u4").view(np.uint8),
        bitorder="little")) for r in masks]
    kmax = max([len(r) for r in rows] + [1])
    out = np.full((len(rows), kmax), sentinel, np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def runtime(n_items: int) -> MapReduceRuntime:
    rt = MapReduceRuntime(impl="vertical", autotune=False)
    rt._n_items = n_items
    return rt


def assert_same(masks: np.ndarray, n_items: int) -> np.ndarray:
    got = runtime(n_items)._padded_indices(masks)
    want = reference_indices(masks, n_items)
    assert got.dtype == want.dtype == np.int32
    assert got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    return got


def masks_of(rows, n_items: int) -> np.ndarray:
    return pack_itemsets([sorted(set(r)) for r in rows], n_items)


@st.composite
def candidate_masks(draw):
    W = draw(st.sampled_from(sorted(N_ITEMS)))
    n_items = N_ITEMS[W]
    kmax = draw(st.integers(1, 10))
    # the first and last bit of each word, and the catalog's last item, are
    # drawn often next to any item
    item = st.one_of(
        st.integers(0, n_items - 1),
        st.sampled_from(sorted({0, 31, n_items - 1,
                                min(32, n_items - 1), n_items - 32})))
    rows = draw(st.lists(st.lists(item, max_size=kmax), max_size=40))
    return masks_of(rows, n_items), n_items


@settings(max_examples=200, deadline=None)
@given(candidate_masks())
def test_index_matches_brute_force(case):
    masks, n_items = case
    assert_same(masks, n_items)


@pytest.mark.parametrize("W", sorted(N_ITEMS))
@pytest.mark.parametrize("k", [1, 2, 5, 10])
def test_index_matches_brute_force_random(W, k):
    n_items = N_ITEMS[W]
    rng = np.random.default_rng(1000 * W + k)
    rows = [rng.choice(n_items, size=rng.integers(1, k + 1), replace=False)
            for _ in range(300)]
    rows += [[n_items - 1], [31], [0, 31, n_items - 1][:k]]
    got = assert_same(masks_of(rows, n_items), n_items)
    assert got.shape[1] == k


def test_zero_rows_come_out_as_sentinel_rows():
    n_items = 1000
    rows = [[3, 999], [], [0, 31, 32, 63, 998], [], [], [500]]
    masks = bucket_pad(masks_of(rows, n_items))      # zero rows at the end too
    got = assert_same(masks, n_items)
    assert got.shape == (masks.shape[0], 5)
    assert got[0].tolist() == [3, 999, n_items, n_items, n_items]
    assert got[2].tolist() == [0, 31, 32, 63, 998]
    assert got[5].tolist() == [500] + [n_items] * 4
    empty = ~masks.any(axis=1)
    assert empty.sum() == masks.shape[0] - 3
    assert (got[empty] == n_items).all()


@pytest.mark.parametrize("shape", [(0, 1), (0, 32), (1, 2), (256, 32)])
def test_only_zero_rows_give_one_sentinel_column(shape):
    masks = np.zeros(shape, np.uint32)
    got = assert_same(masks, 1000)
    assert got.shape == (shape[0], 1)
    assert (got == 1000).all()


def test_index_build_is_timed_on_the_runtime():
    rt = runtime(64)
    rt.scatter_db(masks_of([[0, 1], [1, 63], [0, 63]], 64), n_items=64)
    assert rt.stats.index_seconds == 0.0
    rt.place_candidates(bucket_pad(masks_of([[0, 1], [1, 63]], 64)))
    first = rt.stats.index_seconds
    assert first > 0.0
    rt.place_candidates(bucket_pad(masks_of([[0]], 64)))
    assert rt.stats.index_seconds > first
