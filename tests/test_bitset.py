"""Unit + property tests for the bit-packed itemset algebra."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests degrade to skip without it
from hypothesis import given, settings, strategies as st

from repro.core.bitset import (WORD_BITS, MaskIndex, hash_rows,
                               highest_bit_index, lowest_bit_index,
                               lowest_bits, n_words, pack_itemsets,
                               popcount_rows, singleton_masks, unpack_itemsets)

itemsets_strategy = st.lists(
    st.lists(st.integers(0, 90), min_size=0, max_size=12).map(lambda x: sorted(set(x))),
    min_size=1, max_size=40)


@given(itemsets_strategy)
@settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip(itemsets):
    masks = pack_itemsets(itemsets, 91)
    assert masks.shape == (len(itemsets), n_words(91))
    assert unpack_itemsets(masks) == [tuple(t) for t in itemsets]


@given(itemsets_strategy)
@settings(max_examples=50, deadline=None)
def test_popcount_matches_len(itemsets):
    masks = pack_itemsets(itemsets, 91)
    assert popcount_rows(masks).tolist() == [len(t) for t in itemsets]


@given(itemsets_strategy)
@settings(max_examples=30, deadline=None)
def test_hi_lo_bits(itemsets):
    masks = pack_itemsets(itemsets, 91)
    hi = highest_bit_index(masks)
    lo = lowest_bit_index(masks)
    for i, t in enumerate(itemsets):
        if t:
            assert hi[i] == max(t) and lo[i] == min(t)
        else:
            assert hi[i] == -1 and lo[i] > 91


@given(itemsets_strategy)
@settings(max_examples=30, deadline=None)
def test_lowest_bits_yields_each_rows_lowest_items_in_order(itemsets):
    masks = pack_itemsets(itemsets, 91)
    before = masks.copy()
    steps = list(lowest_bits(masks, 4))
    np.testing.assert_array_equal(masks, before)      # input left as it was
    assert len(steps) == 4
    for i, t in enumerate(itemsets):
        got = [int(word[i]) * WORD_BITS + int(bit[i]).bit_length() - 1
               for word, bit in steps if bit[i]]
        assert got == list(t[:4])
        assert all(int(bit[i]) & (int(bit[i]) - 1) == 0 for _, bit in steps)


def test_singleton_masks():
    s = singleton_masks(70)
    assert popcount_rows(s).tolist() == [1] * 70
    assert unpack_itemsets(s) == [(i,) for i in range(70)]


@given(itemsets_strategy, itemsets_strategy)
@settings(max_examples=30, deadline=None)
def test_mask_index_membership(base, queries):
    bm = pack_itemsets(base, 91)
    qm = pack_itemsets(queries, 91)
    idx = MaskIndex(bm)
    got = idx.contains(qm)
    base_set = {tuple(t) for t in base}
    want = np.array([tuple(t) in base_set for t in queries])
    assert (got == want).all()


def test_hash_distinct():
    rng = np.random.default_rng(0)
    masks = rng.integers(0, 2**32, (5000, 3), dtype=np.uint32)
    masks = np.unique(masks, axis=0)
    h = hash_rows(masks)
    assert len(np.unique(h)) == len(masks)  # no collisions at this scale
