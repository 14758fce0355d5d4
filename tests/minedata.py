"""Small planted-pattern basket sets shared by the mining tests."""

import numpy as np


def planted_baskets(seed=0, n=90, n_items=20):
    """``n`` baskets over ``n_items`` items, each a noisy copy of one of four
    planted patterns, so that several levels are frequent at ``min_sup=0.3``.
    Returns ``(transactions, n_items)``."""
    rng = np.random.default_rng(seed)
    base = rng.random((4, n_items)) < 0.4
    txns = []
    for _ in range(n):
        pat = base[rng.integers(4)]
        row = np.where(rng.random(n_items) < 0.85, pat, rng.random(n_items) < 0.1)
        txns.append(np.nonzero(row)[0].tolist() or [0])
    return txns, n_items
