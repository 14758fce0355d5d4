"""Every pass-combining policy × every CPU counting form × both driver loops
mines exactly what the sequential oracle mines.

Only the levels are compared: ``dpc``, ``etdpc`` and ``measured`` plan their
phases from elapsed time, so dispatch counts and plans may differ run to run,
but the frequent itemsets and their counts may not.
"""

import pytest

from minedata import planted_baskets
from repro.core import mine, sequential_apriori
from repro.core.mapreduce import IMPLS, MapReduceRuntime
from repro.core.policy import ALGORITHMS

MIN_SUP = 0.3

# every counting form but the Pallas ones compiled for the TPU, which raise
# on the CPU: their ``*_interpret`` twins run the same kernels here
CPU_IMPLS = [i for i in IMPLS if "pallas" not in i or i.endswith("_interpret")]


@pytest.fixture(scope="module")
def oracle():
    txns, n_items = planted_baskets()
    return txns, n_items, sequential_apriori(txns, MIN_SUP)


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "unfused"])
@pytest.mark.parametrize("impl", CPU_IMPLS)
@pytest.mark.parametrize("algo", sorted(ALGORITHMS))
def test_mine_matches_oracle(algo, impl, pipeline, oracle):
    txns, n_items, want = oracle
    rt = MapReduceRuntime(impl=impl, autotune=False)
    res = mine(txns, n_items=n_items, min_sup=MIN_SUP, algorithm=algo,
               runtime=rt, pipeline=pipeline)
    got = res.itemsets()
    assert sorted(got) == sorted(want), f"levels {sorted(got)} != {sorted(want)}"
    for k in want:
        assert got[k] == want[k], f"level {k} differs"
