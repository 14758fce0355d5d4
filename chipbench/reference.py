"""Plain exact Apriori: the benchmark's reference.

It imports nothing of the program under test.  Itemsets are sorted item-id
rows; supports are counted on the host from the packed transactions the
benchmark generated: level 1 by column sums, level 2 by one product of the
0/1 item matrix with itself (float32 partial sums of at most ``ROW_BLOCK``
rows, exact), and every later level by ANDing the item columns of each
candidate as 64-bit words and counting the bits.  Candidates come from the
textbook join of two (k-1)-itemsets that share their first k-2 items,
followed by the subset prune.

An itemset is frequent when its count is at least ``min_sup * n_txns``.

``count_precision="bfloat16"`` is the control: every count is rounded to
bfloat16, as a counting path that accumulated or returned its counts in that
type would give them, before the threshold is applied.
"""

from __future__ import annotations

import numpy as np

ROW_BLOCK = 32768


def unpack_bool(masks: np.ndarray, n_items: int) -> np.ndarray:
    b = np.unpackbits(np.ascontiguousarray(masks, "<u4").view(np.uint8),
                      axis=1, bitorder="little")
    return b[:, :n_items].astype(bool)


def to_masks(items: np.ndarray, n_items: int) -> np.ndarray:
    """``(n, k)`` item ids → ``(n, ceil(n_items/32))`` uint32 masks."""
    n = items.shape[0]
    out = np.zeros((n, -(-n_items // 32)), np.uint32)
    rows = np.repeat(np.arange(n), items.shape[1])
    flat = items.reshape(-1)
    np.bitwise_or.at(out, (rows, flat // 32),
                     (np.uint32(1) << (flat % 32).astype(np.uint32)))
    return out


def round_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as int64."""
    f = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    f = (f + 0x7FFF + ((f >> 16) & 1)) & 0xFFFF0000
    return f.astype(np.uint32).view(np.float32).astype(np.int64)


def _keys(items: np.ndarray, base: int) -> np.ndarray:
    key = np.zeros(items.shape[0], np.int64)
    for j in range(items.shape[1]):
        key = key * base + items[:, j]
    return key


def apriori_gen(prev: np.ndarray, base: int) -> np.ndarray:
    """Join (k-1)-itemsets (sorted rows, sorted lexicographically) sharing
    their first k-2 items, then keep the candidates whose every
    (k-1)-subset is in ``prev``."""
    n, km1 = prev.shape
    if n < 2:
        return np.zeros((0, km1 + 1), np.int64)
    prefix = _keys(prev[:, :-1], base) if km1 > 1 else np.zeros(n, np.int64)
    starts = np.flatnonzero(np.r_[True, prefix[1:] != prefix[:-1]])
    ends = np.r_[starts[1:], n]
    parts = []
    for s, e in zip(starts, ends):
        g = e - s
        if g < 2:
            continue
        i, j = np.triu_indices(g, 1)
        parts.append(np.concatenate(
            [prev[s + i], prev[s + j, -1:]], axis=1))
    if not parts:
        return np.zeros((0, km1 + 1), np.int64)
    cands = np.concatenate(parts)
    known = np.sort(_keys(prev, base))
    keep = np.ones(cands.shape[0], bool)
    for drop in range(km1 - 1):          # the last two subsets are the parents
        sub = np.delete(cands, drop, axis=1)
        keep &= np.isin(_keys(sub, base), known, assume_unique=False)
    return cands[keep]


def _count_level2(x: np.ndarray) -> np.ndarray:
    f = x.shape[1]
    g = np.zeros((f, f), np.int64)
    for lo in range(0, x.shape[0], ROW_BLOCK):
        xb = x[lo:lo + ROW_BLOCK].astype(np.float32)
        g += (xb.T @ xb).astype(np.int64)
    return g


def _vertical(x: np.ndarray) -> np.ndarray:
    """``(N, F)`` bool → ``(F, ceil(N/64))`` uint64 item columns."""
    n = x.shape[0]
    pad = (-n) % 64
    if pad:
        x = np.concatenate([x, np.zeros((pad, x.shape[1]), bool)])
    return np.ascontiguousarray(
        np.packbits(x.T, axis=1, bitorder="little")).view("<u8")


def _count_columns(vert: np.ndarray, cands: np.ndarray,
                   block: int = 256) -> np.ndarray:
    out = np.empty(cands.shape[0], np.int64)
    for lo in range(0, cands.shape[0], block):
        c = cands[lo:lo + block]
        acc = vert[c[:, 0]].copy()
        for j in range(1, c.shape[1]):
            acc &= vert[c[:, j]]
        out[lo:lo + block] = np.bitwise_count(acc).sum(axis=1)
    return out


def mine(masks: np.ndarray, n_items: int, min_sup: float,
         count_precision: str = "exact") -> dict:
    """Frequent itemsets of the packed transactions ``masks``:
    ``{k: (items (n, k) int64, counts (n,) int64)}``, rows sorted."""
    if count_precision not in ("exact", "bfloat16"):
        raise ValueError(f"unknown count precision {count_precision!r}")
    rnd = round_bfloat16 if count_precision == "bfloat16" else (lambda c: c)
    n_txns = masks.shape[0]
    min_count = min_sup * n_txns
    x = unpack_bool(masks, n_items)
    c1 = rnd(x.sum(axis=0).astype(np.int64))
    freq = np.flatnonzero(c1 >= min_count)
    levels = {}
    if freq.size:
        levels[1] = (freq[:, None].astype(np.int64), c1[freq])
    if freq.size < 2:
        return levels
    x = x[:, freq]                        # columns of frequent items only
    g = rnd(_count_level2(x))
    i, j = np.triu_indices(freq.size, 1)
    keep = g[i, j] >= min_count
    local = np.stack([i[keep], j[keep]], axis=1)
    counts = g[i, j][keep]
    base = n_items + 1
    vert = None
    k = 2
    while local.shape[0]:
        levels[k] = (freq[local].astype(np.int64), counts)
        cands = apriori_gen(local, base)
        if cands.shape[0] == 0:
            break
        if vert is None:
            vert = _vertical(x)
        c = rnd(_count_columns(vert, cands))
        keep = c >= min_count
        local, counts = cands[keep], c[keep]
        k += 1
    return levels

