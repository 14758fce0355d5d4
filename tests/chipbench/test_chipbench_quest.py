"""The benchmark's generator: the Quest model's parameters, the configured
rows, items and mean width, and a seed that presents one data set in
another order."""

import numpy as np
import pytest

from chipbench import quest, reference

PARAMS = dict(n_items=300, avg_width=12, n_patterns=100, avg_pattern_len=5,
              correlation=0.5, corruption_mean=0.5, corruption_var=0.1,
              data_seed=0)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_same_seed_same_rows(seed):
    a = quest.generate(n_txns=5000, seed=seed, **PARAMS)
    b = quest.generate(n_txns=5000, seed=seed, **PARAMS)
    assert a.dtype == np.uint32 and a.shape == (5000, 10)
    assert np.array_equal(a, b)


def test_other_seed_other_rows():
    a = quest.generate(n_txns=2000, seed=1, **PARAMS)
    b = quest.generate(n_txns=2000, seed=2, **PARAMS)
    assert not np.array_equal(a, b)


def test_rows_items_and_mean_width():
    m = quest.generate(n_txns=20000, seed=3, **PARAMS)
    mat = reference.unpack_bool(m, PARAMS["n_items"])
    width = mat.sum(axis=1)
    assert mat.shape == (20000, 300)
    assert abs(width.mean() - PARAMS["avg_width"]) < 0.1 * PARAMS["avg_width"]
    assert width.min() >= 1
    # no bit beyond the catalog
    assert not reference.unpack_bool(m, 320)[:, 300:].any()


def test_every_seed_mines_the_same_work():
    """A seed permutes the rows and relabels the items of one data set, so
    the widths and the item frequencies, and the level sizes, are the same
    for every seed."""
    a = reference.unpack_bool(quest.generate(n_txns=4000, seed=5, **PARAMS),
                              300)
    b = reference.unpack_bool(quest.generate(n_txns=4000, seed=6, **PARAMS),
                              300)
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a.sum(axis=1)), np.sort(b.sum(axis=1)))
    assert np.array_equal(np.sort(a.sum(axis=0)), np.sort(b.sum(axis=0)))
    la = reference.mine(quest.pack(a), 300, 0.02)
    lb = reference.mine(quest.pack(b), 300, 0.02)
    assert {k: len(v[1]) for k, v in la.items()} == \
        {k: len(v[1]) for k, v in lb.items()}
    assert max(la) >= 2


def test_pattern_model_parameters():
    """Pattern sizes around |I|, corruption levels from N(0.5, 0.1), and
    patterns that share items with the one before far above chance."""
    rng = np.random.default_rng(0)
    table, sizes, cum_w, corrupt = quest.make_patterns(
        rng, 1000, 4000, 4, 0.5, 0.5, 0.1)
    assert abs(sizes.mean() - 4) < 0.15
    assert np.all(np.diff(cum_w) >= 0) and cum_w[-1] == pytest.approx(1.0)
    inner = corrupt[(corrupt > 0) & (corrupt < 1)]
    assert abs(inner.mean() - 0.5) < 0.03
    assert abs(corrupt.var() - 0.1) < 0.02
    shared = [np.intersect1d(table[i][table[i] >= 0],
                             table[i - 1][table[i - 1] >= 0]).size > 0
              for i in range(1, 4000)]
    assert np.mean(shared) > 0.4            # chance alone: under 2%


def test_patterns_drive_the_rows():
    """Items of the heaviest pattern co-occur far above independence."""
    p = dict(PARAMS)
    rng = quest._rng(p.pop("data_seed"), 0)
    table, _, cum, _ = quest.make_patterns(
        rng, p["n_items"], p["n_patterns"], p["avg_pattern_len"],
        p["correlation"], p["corruption_mean"], p["corruption_var"])
    top = table[np.argmax(np.diff(np.r_[0.0, cum]))]
    top = top[top >= 0][:2]
    mat = quest.dataset(n_txns=20000, **PARAMS)
    f = mat.mean(axis=0)
    joint = (mat[:, top[0]] & mat[:, top[1]]).mean()
    assert joint > 3 * f[top[0]] * f[top[1]]
