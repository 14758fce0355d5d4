"""Candidates counted per frequent itemset found, over the mines of the
window (``MiningResult.phases``): the waste that pruning trades against."""

from chipbench import layer


def read(ctx):
    mines = layer.mines(ctx)
    if not mines:
        return None
    cands = sum(sum(p.candidate_counts) for r in mines for p in r.phases)
    freq = sum(sum(p.frequent_counts) for r in mines for p in r.phases)
    return cands / freq if freq else None
