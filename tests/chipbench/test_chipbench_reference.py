"""The plain reference against the program's sequential oracle, and its
bfloat16 control, which must come out wrong."""

import numpy as np
import pytest

from chipbench import quest, reference

PARAMS = dict(n_items=48, avg_width=8, n_patterns=12, avg_pattern_len=4,
              correlation=0.5, corruption_mean=0.5, corruption_var=0.1,
              data_seed=0)


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_reference_matches_sequential_apriori(seed):
    from repro.core import sequential_apriori
    m = quest.generate(n_txns=1500, seed=seed, **PARAMS)
    got = reference.mine(m, PARAMS["n_items"], 0.06)
    txns = [tuple(np.flatnonzero(r))
            for r in reference.unpack_bool(m, PARAMS["n_items"])]
    want = sequential_apriori(txns, 0.06)
    assert {k: {tuple(int(i) for i in row): int(c)
                for row, c in zip(*got[k])} for k in got} == want
    assert max(got) >= 3


def test_round_bfloat16():
    x = np.array([0, 1, 255, 256, 257, 1000, 1001, 4097, 131071])
    assert reference.round_bfloat16(x).tolist() == [
        0, 1, 255, 256, 256, 1000, 1000, 4096, 131072]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_is_not_correct(seed):
    """The control: the reference with bfloat16 counts, at a size where
    counts pass 256, reads mismatches against the exact reference."""
    from chipbench.modes.mine import level_table, mismatches
    m = quest.generate(n_txns=6000, seed=seed, **PARAMS)
    exact = reference.mine(m, PARAMS["n_items"], 0.06)
    control = reference.mine(m, PARAMS["n_items"], 0.06, "bfloat16")
    n = PARAMS["n_items"]
    assert mismatches(level_table(control, n), level_table(exact, n)) > 0
    assert mismatches(level_table(exact, n), level_table(exact, n)) == 0
