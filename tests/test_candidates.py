"""Candidate generation vs brute-force set semantics (property-based)."""

from itertools import combinations

import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core import candidates
from repro.core.bitset import pack_itemsets, unpack_itemsets
from repro.core.candidates import (apriori_gen, join, non_apriori_gen, prune,
                                   speculative_join)

N_ITEMS = 40


def brute_join(prev_sets, k_prev):
    """Classic F_{k-1}×F_{k-1} join on sorted tuples."""
    prev = sorted(prev_sets)
    out = set()
    for i in range(len(prev)):
        for j in range(i + 1, len(prev)):
            a, b = prev[i], prev[j]
            if a[:-1] == b[:-1] and a[-1] != b[-1]:
                out.add(tuple(sorted(set(a) | set(b))))
    return out


def brute_prune(cands, prev_sets, k_prev):
    prev = set(prev_sets)
    return {c for c in cands
            if all(sub in prev for sub in combinations(c, k_prev))}


def ksets(k):
    return st.lists(
        st.lists(st.integers(0, N_ITEMS - 1), min_size=k, max_size=k,
                 unique=True).map(lambda x: tuple(sorted(x))),
        min_size=0, max_size=25, unique=True)


@given(ksets(3))
@settings(max_examples=40, deadline=None)
def test_join_matches_bruteforce(prev):
    masks = pack_itemsets([list(t) for t in prev], N_ITEMS)
    got = set(unpack_itemsets(join(masks, 3)))
    assert got == brute_join(prev, 3)


@given(ksets(2))
@settings(max_examples=40, deadline=None)
def test_apriori_gen_matches_bruteforce(prev):
    masks = pack_itemsets([list(t) for t in prev], N_ITEMS)
    got = set(unpack_itemsets(apriori_gen(masks, 2)))
    want = brute_prune(brute_join(prev, 2), prev, 2)
    assert got == want


@given(ksets(3))
@settings(max_examples=40, deadline=None)
def test_non_apriori_gen_superset(prev):
    """join-only output ⊇ join+prune output (the skipped-pruning invariant)."""
    masks = pack_itemsets([list(t) for t in prev], N_ITEMS)
    unpruned = set(unpack_itemsets(non_apriori_gen(masks, 3)))
    pruned = set(unpack_itemsets(apriori_gen(masks, 3)))
    assert pruned <= unpruned


def test_join_blocked_consistency():
    """Blocked evaluation must be independent of block size."""
    rng = np.random.default_rng(0)
    sets = {tuple(sorted(rng.choice(N_ITEMS, 4, replace=False))) for _ in range(300)}
    masks = pack_itemsets([list(t) for t in sets], N_ITEMS)
    a = set(unpack_itemsets(join(masks, 4, block=7)))
    b = set(unpack_itemsets(join(masks, 4, block=1024)))
    assert a == b


def test_prune_keeps_frequent_closure():
    prev = [(0, 1), (0, 2), (1, 2), (3, 4)]
    masks = pack_itemsets([list(t) for t in prev], N_ITEMS)
    c = join(masks, 2)
    kept = set(unpack_itemsets(prune(c, masks, 2)))
    assert kept == {(0, 1, 2)}


@pytest.mark.parametrize("k", [1, 2, 3, 4])
@given(data=st.data())
@settings(max_examples=30, deadline=None)
def test_prune_of_join_matches_bruteforce(k, data):
    prev = data.draw(ksets(k))
    masks = pack_itemsets([list(t) for t in prev], N_ITEMS)
    got = set(unpack_itemsets(prune(join(masks, k), masks, k)))
    assert got == brute_prune(brute_join(prev, k), prev, k)


def _dense_level(rng, k, n_items, n):
    """A level of ``k``-itemsets that joins often and prunes some: most
    ``k``-subsets of a few random patterns, plus ``n`` random ``k``-sets."""
    sets = set()
    for _ in range(6):
        pattern = rng.choice(n_items, k + 2, replace=False)
        sets |= {tuple(sorted(s)) for s in combinations(pattern, k)
                 if rng.random() < 0.85}
    sets |= {tuple(sorted(rng.choice(n_items, k, replace=False)))
             for _ in range(n)}
    sets = sorted(sets)
    return sets, pack_itemsets([list(t) for t in sets], n_items)


@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("n_items", [12, 70])   # one word, then three
def test_prune_keeps_rows_and_order_of_a_full_subset_check(k, n_items):
    """Row for row, the same result as checking all ``k+1`` subsets."""
    rng = np.random.default_rng(k * 100 + n_items)
    prev, masks = _dense_level(rng, k, n_items, 160)
    c = join(masks, k)
    assert c.shape[0] > 0
    prev_set = set(prev)
    full = np.array([all(s in prev_set for s in combinations(t, k))
                     for t in unpack_itemsets(c)], dtype=bool)
    assert 0 < full.sum() < c.shape[0]
    np.testing.assert_array_equal(prune(c, masks, k), c[full])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prune_of_a_resolved_speculative_join_matches_bruteforce(seed):
    """``prune`` after ``SpecJoin.resolve``: the join of the kept rows of a
    candidate level, as the pipelined loop hands it over."""
    rng = np.random.default_rng(seed)
    cand_sets, cand_masks = _dense_level(rng, 3, 14, 200)
    keep = rng.random(len(cand_sets)) < 0.6
    level = [t for t, kept in zip(cand_sets, keep) if kept]
    spec = speculative_join(cand_masks, 3)
    resolved = spec.resolve(keep)
    got = set(unpack_itemsets(prune(resolved, cand_masks[keep], 3)))
    assert got == brute_prune(brute_join(level, 3), level, 3)
    assert got


def test_level2_prune_returns_its_input_without_a_probe(monkeypatch):
    """Every 2-candidate is the union of its two parents: nothing to look up."""
    masks = pack_itemsets([[i] for i in range(0, 40, 3)], N_ITEMS)
    c = join(masks, 1)

    def no_index(*a, **k):
        raise AssertionError("level-2 prune built an index")

    monkeypatch.setattr(candidates, "MaskIndex", no_index)
    np.testing.assert_array_equal(prune(c, masks, 1), c)


@pytest.mark.parametrize("k", [2, 3, 4])
def test_prune_probes_each_candidate_k_minus_1_times(k, monkeypatch):
    """``len(cands) * (k - 1)`` lookups, the count ``run_phase`` records as
    ``prune_probes``; none of them a parent of the candidate."""
    rng = np.random.default_rng(k)
    prev, masks = _dense_level(rng, k, 12, 160)
    c = join(masks, k)
    probed = []
    real = candidates.MaskIndex.contains

    def spy(self, queries):
        probed.append(np.array(queries))
        return real(self, queries)

    monkeypatch.setattr(candidates.MaskIndex, "contains", spy)
    prune(c, masks, k)
    assert sum(q.shape[0] for q in probed) == c.shape[0] * (k - 1)
    cand_items = unpack_itemsets(c)
    for q in probed:
        for t, sub in zip(cand_items, unpack_itemsets(q)):
            assert set(sub) < set(t) and len(sub) == k
            dropped = (set(t) - set(sub)).pop()
            assert dropped in t[:k - 1]          # a shared-prefix item
