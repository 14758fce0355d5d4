"""IBM Quest synthetic transactions, the benchmark's own generator.

The model is the one Agrawal & Srikant state (Fast Algorithms for Mining
Association Rules, VLDB 1994, section 2.4.3):

- ``n_patterns`` (|L|) potentially large itemsets.  Each has a Poisson size
  with mean ``avg_pattern_len`` (|I|, at least 1).  The first one's items
  are uniform over the ``n_items`` (N); of each later one, a fraction drawn
  from an exponential with mean ``correlation`` (the nearest whole number of
  items, at most all) comes from the previous pattern, the rest is uniform.
- Each pattern has a weight, exponential with unit mean and normalised to
  sum 1, and a corruption level drawn from a normal distribution with mean
  ``corruption_mean`` and variance ``corruption_var``, clipped to [0, 1].
- Each transaction has a Poisson size with mean ``avg_width`` (|T|, at
  least 1).  It is filled with patterns picked by weight.  When a pattern is
  added, its items are dropped one at a time, in random order, as long as a
  uniform draw is below its corruption level.  A pattern that does not fit
  in what is left of the transaction is put in anyway half of the time and
  otherwise moved to the next transaction; a transaction that holds nothing
  yet always takes it.

The data set is fixed by ``data_seed``, as a published data set is one file.
The run's ``seed`` presents it in another order: a permutation of the rows
and a relabelling of the items, both drawn from the seed.  So every seed
mines the same amount of work, on inputs that differ in every bit position.

Rows come as packed masks: ``(N, ceil(n_items/32))`` uint32, bit ``i % 32``
of word ``i // 32`` set when item ``i`` is present.  Only the walk that
assigns patterns to transactions is a loop over integers; the items are
drawn and placed in bulk with NumPy.
"""

from __future__ import annotations

import numpy as np

DRAW_CHUNK = 1 << 17


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([seed & (2**64 - 1), *stream]))


def n_words(n_items: int) -> int:
    return -(-n_items // 32)


def make_patterns(rng, n_items: int, n_patterns: int, avg_pattern_len: float,
                  correlation: float, corruption_mean: float,
                  corruption_var: float):
    """The potentially large itemsets as a ``(n_patterns, L)`` int table
    padded with -1, their sizes, cumulative weights and corruption levels."""
    sizes = np.clip(rng.poisson(avg_pattern_len, n_patterns), 1, n_items)
    fracs = rng.exponential(correlation, n_patterns)
    table = np.full((n_patterns, int(sizes.max())), -1, np.int64)
    prev = np.empty(0, np.int64)
    for i, size in enumerate(sizes):
        n_prev = min(int(round(fracs[i] * size)), size, prev.size)
        kept = rng.choice(prev, n_prev, replace=False)
        rest = np.setdiff1d(np.arange(n_items), kept)
        pat = np.sort(np.concatenate(
            [kept, rng.choice(rest, size - n_prev, replace=False)]))
        table[i, :size] = pat
        prev = pat
    w = rng.exponential(1.0, n_patterns)
    cum_w = np.cumsum(w / w.sum())
    corrupt = np.clip(rng.normal(corruption_mean, np.sqrt(corruption_var),
                                 n_patterns), 0.0, 1.0)
    return table, sizes, cum_w, corrupt


def _draws(rng, n: int, sizes, cum_w, corrupt):
    """``n`` weighted picks of a pattern: ids, items kept after corruption
    and the coin that says whether a pattern that does not fit goes in."""
    pid = np.minimum(np.searchsorted(cum_w, rng.random(n)), cum_w.size - 1)
    c = corrupt[pid]
    u = 1.0 - rng.random(n)                     # in (0, 1]
    with np.errstate(divide="ignore", invalid="ignore"):
        # dropped while a uniform draw < c: P(drops >= d) = c**d
        drops = np.where(c <= 0.0, 0.0,
                         np.where(c >= 1.0, np.inf, np.log(u) / np.log(c)))
    kept = np.maximum(sizes[pid] - np.minimum(np.floor(drops), 1e9), 0)
    return pid, kept.astype(np.int64), rng.random(n) < 0.5


def _assign(rng, txn_sizes, sizes, cum_w, corrupt):
    """Walk the transactions and give each pattern pick its transaction:
    ``(pattern ids, kept counts, owner transaction)`` of the picks used."""
    pids, kepts, owners = [], [], []
    pid, kept, coin = _draws(rng, DRAW_CHUNK, sizes, cum_w, corrupt)
    owner = np.full(DRAW_CHUNK, -1, np.int64)
    kept_l, coin_l, owner_l = kept.tolist(), coin.tolist(), owner.tolist()
    j, carry = 0, -1
    for t, size in enumerate(txn_sizes.tolist()):
        n = 0
        while True:
            if carry >= 0:
                i, carry = carry, -1
            else:
                if j == len(kept_l):
                    pids.append(pid)
                    kepts.append(kept)
                    owners.append(np.array(owner_l, np.int64))
                    pid, kept, coin = _draws(rng, DRAW_CHUNK, sizes, cum_w,
                                             corrupt)
                    kept_l, coin_l = kept.tolist(), coin.tolist()
                    owner_l = [-1] * DRAW_CHUNK
                    j = 0
                i = j
                j += 1
            m = kept_l[i]
            if m == 0:
                continue
            if n == 0 or n + m <= size:
                owner_l[i] = t
                n += m
                if n >= size:
                    break
            elif coin_l[i]:
                owner_l[i] = t
                break
            else:
                carry = i
                break
    pids.append(pid)
    kepts.append(kept)
    owners.append(np.array(owner_l, np.int64))
    pid, kept, owner = (np.concatenate(x) for x in (pids, kepts, owners))
    used = owner >= 0
    return pid[used], kept[used], owner[used]


def dataset(*, n_txns: int, n_items: int, avg_width: float, n_patterns: int,
            avg_pattern_len: float, correlation: float,
            corruption_mean: float, corruption_var: float,
            data_seed: int) -> np.ndarray:
    """The data set as an ``(n_txns, n_items)`` bool matrix."""
    rng = _rng(data_seed, 0)
    table, sizes, cum_w, corrupt = make_patterns(
        rng, n_items, n_patterns, avg_pattern_len, correlation,
        corruption_mean, corruption_var)
    txn_sizes = np.maximum(rng.poisson(avg_width, n_txns), 1)
    pid, kept, owner = _assign(rng, txn_sizes, sizes, cum_w, corrupt)
    mat = np.zeros((n_txns, n_items), bool)
    flat = mat.reshape(-1)
    width = table.shape[1]
    for lo in range(0, pid.size, DRAW_CHUNK):
        p, m, o = (a[lo:lo + DRAW_CHUNK] for a in (pid, kept, owner))
        items = table[p]
        keys = np.where(items >= 0, rng.random(items.shape), 2.0)
        items = np.take_along_axis(items, np.argsort(keys, axis=1), axis=1)
        keep = np.arange(width) < m[:, None]    # the first m in random order
        flat[np.repeat(o, m) * n_items + items[keep]] = True
    return mat


def pack(mat: np.ndarray) -> np.ndarray:
    """``(N, I)`` bool → ``(N, ceil(I/32))`` uint32 masks."""
    n, items = mat.shape
    pad = n_words(items) * 32 - items
    if pad:
        mat = np.concatenate([mat, np.zeros((n, pad), bool)], axis=1)
    bits = np.packbits(np.ascontiguousarray(mat), axis=1, bitorder="little")
    return bits.view("<u4").astype(np.uint32)


def generate(*, seed: int, **params) -> np.ndarray:
    """The configured data set (``params``, see :func:`dataset`), its rows
    permuted and its items relabelled by ``seed``, as packed masks."""
    mat = dataset(**params)
    rng = _rng(seed, 1)
    mat = mat[rng.permutation(mat.shape[0])]
    return pack(mat[:, rng.permutation(mat.shape[1])])
