"""Bit-packed itemset algebra.

Transactions and candidate itemsets are represented as bitmasks over the item
catalog, packed into ``W = ceil(n_items / 32)`` uint32 words.  This replaces the
paper's prefix-tree (trie): on TPU there is no efficient pointer chasing, and the
trie's role — cheap subset testing of a transaction against many candidates — is
played by a dense, word-parallel ``(c & t) == c`` test that maps onto the VPU.

All host-side helpers are numpy (numpy >= 2.0 provides ``np.bitwise_count``);
device-side equivalents live next to them with a ``j``-prefix and use
``jax.lax.population_count`` / ``jax.lax.clz``.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

WORD_BITS = 32


def n_words(n_items: int) -> int:
    """Number of uint32 words needed for an ``n_items``-wide bitmask."""
    return (n_items + WORD_BITS - 1) // WORD_BITS


def pack_itemsets(itemsets, n_items: int) -> np.ndarray:
    """Pack an iterable of item-index iterables into an ``(N, W)`` uint32 array."""
    W = n_words(n_items)
    out = np.zeros((len(itemsets), W), dtype=np.uint32)
    for row, items in enumerate(itemsets):
        for it in items:
            if not 0 <= it < n_items:
                raise ValueError(f"item {it} out of range [0, {n_items})")
            out[row, it // WORD_BITS] |= np.uint32(1 << (it % WORD_BITS))
    return out


def unpack_itemsets(masks: np.ndarray) -> list[tuple[int, ...]]:
    """Inverse of :func:`pack_itemsets` — sorted item tuples per row."""
    masks = np.asarray(masks, dtype=np.uint32)
    out = []
    for row in masks:
        items = []
        for wi, word in enumerate(row):
            word = int(word)
            while word:
                low = word & -word
                items.append(wi * WORD_BITS + low.bit_length() - 1)
                word ^= low
        out.append(tuple(items))
    return out


def popcount_rows(masks: np.ndarray) -> np.ndarray:
    """Per-row popcount of an ``(N, W)`` uint32 array → ``(N,)`` int32."""
    return np.bitwise_count(np.asarray(masks, dtype=np.uint32)).sum(axis=1).astype(np.int32)


def singleton_masks(n_items: int) -> np.ndarray:
    """``(n_items, W)`` masks with exactly one bit set each (the 1-itemsets)."""
    W = n_words(n_items)
    out = np.zeros((n_items, W), dtype=np.uint32)
    idx = np.arange(n_items)
    out[idx, idx // WORD_BITS] = np.uint32(1) << (idx % WORD_BITS).astype(np.uint32)
    return out


def floor_log2(x: np.ndarray) -> np.ndarray:
    """floor(log2(x)) for positive ints via the float64 exponent field.

    Exact for x < 2^53 (uint32 qualifies); ~3× faster than np.log2 because it
    is a cast + shift + mask instead of a transcendental (§Perf iteration M-A).
    Zeros map to -1023-ish garbage — callers must mask.
    """
    f = x.astype(np.float64)
    return ((f.view(np.uint64) >> np.uint64(52)).astype(np.int64) & 0x7FF) - 1023


def highest_bit_index(masks: np.ndarray) -> np.ndarray:
    """Index of the highest set bit per ``(..., W)`` mask; -1 for empty masks."""
    masks = np.asarray(masks, dtype=np.uint32)
    *lead, W = masks.shape
    hi = np.full(lead, -1, dtype=np.int64)
    for wi in range(W):
        word = masks[..., wi].astype(np.int64)
        nz = word != 0
        if not nz.any():
            continue
        bl = floor_log2(np.where(nz, word, 1))
        hi = np.where(nz, wi * WORD_BITS + bl, hi)
    return hi


def lowest_bit_index(masks: np.ndarray) -> np.ndarray:
    """Index of the lowest set bit per ``(..., W)`` mask; ``W*32 + 1`` sentinel
    for empty masks."""
    masks = np.asarray(masks, dtype=np.uint32)
    *lead, W = masks.shape
    sentinel = W * WORD_BITS + 1
    lo = np.full(lead, sentinel, dtype=np.int64)
    for wi in range(W):
        word = masks[..., wi].astype(np.int64)
        nz = (word != 0) & (lo == sentinel)   # first word with a set bit wins
        if not nz.any():
            continue
        bl = floor_log2(np.where(nz, word & -word, 1))
        lo = np.where(nz, wi * WORD_BITS + bl, lo)
    return lo


def lowest_bits(masks: np.ndarray, n: int):
    """Yield each row's ``n`` lowest set bits, lowest first, as ``(word, bit)``:
    ``(N,)`` word indices and ``(N,)`` uint32 one-bit words.

    Word-level: one pass over ``(N, W)`` per bit, no ``(N, W*32)`` expansion.
    A row with fewer than ``n`` bits yields a zero ``bit`` past its last one.
    """
    rest = np.array(masks, dtype=np.uint32)
    rows = np.arange(rest.shape[0])
    for _ in range(n):
        word = np.argmax(rest != 0, axis=1)
        w = rest[rows, word]
        bit = w & -w
        rest[rows, word] = w ^ bit
        yield word, bit


# ---------------------------------------------------------------------------
# 64-bit order-independent-ish hashing of masks (host side, for membership).
# Rows are hashed word-by-word with distinct odd multipliers, so the hash is a
# function of the full (ordered) word vector — i.e. of the exact itemset.
# ---------------------------------------------------------------------------

_MULTS = np.array(
    [0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1, 0x9E3779B9,
     0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2D, 0x165667C5, 0xA2B2AE3B, 0x37D4EB2F],
    dtype=np.uint64,
)


def hash_rows(masks: np.ndarray) -> np.ndarray:
    """64-bit hash per row of an ``(N, W)`` uint32 array."""
    masks = np.asarray(masks, dtype=np.uint32)
    W = masks.shape[1]
    if W > len(_MULTS):  # extend multipliers deterministically
        reps = -(-W // len(_MULTS))
        mults = np.tile(_MULTS, reps)[:W]
    else:
        mults = _MULTS[:W]
    h = np.zeros(masks.shape[0], dtype=np.uint64)
    for wi in range(W):
        h ^= (masks[:, wi].astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)) * mults[wi]
        h ^= h >> np.uint64(29)
        h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(32)
    return h


class MaskIndex:
    """Sorted-hash membership index over a set of masks.

    Hash collisions are resolved exactly: every probe verifies full word
    equality over the run of equal hashes.
    """

    def __init__(self, masks: np.ndarray):
        self.masks = np.asarray(masks, dtype=np.uint32)
        h = hash_rows(self.masks)
        self._order = np.argsort(h, kind="stable")
        self.sorted_hashes = h[self._order]
        self.sorted_masks = self.masks[self._order]

    def __len__(self) -> int:
        return self.masks.shape[0]

    def find(self, queries: np.ndarray) -> np.ndarray:
        """Vectorized exact lookup → (Q,) int64 row index into the original
        ``masks`` array, or -1 where a query mask is absent."""
        queries = np.asarray(queries, dtype=np.uint32)
        out = np.full(queries.shape[0], -1, dtype=np.int64)
        if len(self) == 0 or queries.shape[0] == 0:
            return out
        qh = hash_rows(queries)
        left = np.searchsorted(self.sorted_hashes, qh, side="left")
        pending = np.arange(queries.shape[0])
        offset = 0
        # Walk equal-hash runs; in practice the first probe resolves ~all rows.
        while pending.size:
            pos = left[pending] + offset
            valid = pos < len(self.sorted_hashes)
            vpend = pending[valid]
            vpos = pos[valid]
            same_hash = self.sorted_hashes[vpos] == qh[vpend]
            vpend = vpend[same_hash]
            vpos = vpos[same_hash]
            if vpend.size == 0:
                break
            eq = (self.sorted_masks[vpos] == queries[vpend]).all(axis=1)
            out[vpend[eq]] = self._order[vpos[eq]]
            pending = vpend[~eq]
            offset += 1
        return out

    def contains(self, queries: np.ndarray) -> np.ndarray:
        """Vectorized exact membership test → (Q,) bool."""
        return self.find(queries) >= 0


def vertical_pack(db_masks: np.ndarray, n_items: int) -> np.ndarray:
    """Vertical (item-major) bitmap layout: row i = bitmap of transactions
    containing item i, packed along transactions.

    Returns ``(n_items + 1, Tw)`` uint32, ``Tw = ceil(N/32)``.  The extra last
    row is the **valid-transaction mask** (1 for every real transaction) — it
    doubles as the AND-identity used to pad variable-length candidates.

    support(candidate) = popcount(AND of its item rows) — §Perf iteration M-D
    (the vertical data layout of Jen et al., the paper's related work [15]).
    """
    db_masks = np.asarray(db_masks, dtype=np.uint32)
    n, W = db_masks.shape
    Tw = (n + WORD_BITS - 1) // WORD_BITS
    # expand to a (n_items+1, N) bit matrix (last row = valid mask), then
    # pack along transactions (little bit-order → uint32 view is bit j%32 of
    # word j//32, matching the horizontal convention)
    shifts = np.arange(WORD_BITS, dtype=np.uint32)
    bits = ((db_masks[:, :, None] >> shifts[None, None, :]) & np.uint32(1))
    bits = bits.reshape(n, W * WORD_BITS)[:, :n_items].astype(np.uint8)
    bits = np.concatenate([bits, np.ones((n, 1), np.uint8)], axis=1)  # valid
    bt = np.ascontiguousarray(bits.T)                 # (n_items+1, N)
    pad = Tw * WORD_BITS - n
    if pad:
        bt = np.concatenate([bt, np.zeros((bt.shape[0], pad), np.uint8)], axis=1)
    packed = np.packbits(bt, axis=1, bitorder="little")
    return np.ascontiguousarray(packed.view(np.uint32))


# ---------------------------------------------------------------------------
# Device-side (jnp) equivalents.
# ---------------------------------------------------------------------------

def jpopcount_rows(masks: jax.Array) -> jax.Array:
    """Per-row popcount on device → (N,) int32."""
    return jax.lax.population_count(masks.astype(jnp.uint32)).astype(jnp.int32).sum(axis=-1)


def junpack_bits(masks: jax.Array) -> jax.Array:
    """Bit-plane unpack on device: ``(..., W) uint32 → (..., W*32) int8``.

    Column ``w*32 + b`` of the output is bit ``b`` of word ``w`` — the same
    little bit-order every packed layout in this repo uses, so
    ``junpack_bits(pack_itemsets(s, n))[:, i]`` is the indicator of item ``i``
    (columns ≥ ``n_items`` are zero).  This is the shared unpack behind the
    matmul counting forms (DESIGN.md §10): containment becomes
    ``count(c, t) = Σ_b c_bits[b]·t_bits[b] == popcount(c)`` and the sum is an
    int8 ``dot_general`` the MXU/tensor cores execute natively.
    """
    m = masks.astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (m[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*m.shape[:-1], m.shape[-1] * WORD_BITS).astype(jnp.int8)


def junpack_bits_lanes(masks: jax.Array) -> jax.Array:
    """:func:`junpack_bits` with the word axis zero-padded to a multiple of 4,
    so the bit-plane axis is a multiple of 128 lanes — the int8 tiling the
    Pallas matmul kernels need.  Zero planes add nothing to any overlap."""
    pad = (-masks.shape[-1]) % 4
    if pad:
        masks = jnp.pad(masks, [(0, 0)] * (masks.ndim - 1) + [(0, pad)])
    return junpack_bits(masks)


def jpack_bits(bits: jax.Array) -> jax.Array:
    """Inverse of :func:`junpack_bits`: ``(..., B) int8/bool → (..., ceil(B/32))
    uint32`` (B is zero-padded up to the word multiple)."""
    B = bits.shape[-1]
    pad = (-B) % WORD_BITS
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((*bits.shape[:-1], pad), bits.dtype)], axis=-1)
    words = bits.reshape(*bits.shape[:-1], -1, WORD_BITS).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return (words << shifts).sum(axis=-1).astype(jnp.uint32)


def jvertical_pack(rows: jax.Array, n_items: int) -> jax.Array:
    """Device-side :func:`vertical_pack`: ``(n, W)`` uint32 rows →
    ``(n_items + 1, Tw)`` uint32, the same bytes (the valid row last, zero
    bits past row ``n`` in the last word).

    Rows are zero-padded to ``Tw·32`` and viewed as ``(W, 32 rows, Tw)``;
    word ``tw`` of item ``w·32 + b`` is then ``Σ_r bit b of row tw·32+r << r``,
    a 32×32 bit transpose per (word, row block), with no ``(n, W·32)``
    bit matrix in between."""
    n, W = rows.shape
    Tw = -(-n // WORD_BITS)
    x = jnp.pad(rows.astype(jnp.uint32), ((0, Tw * WORD_BITS - n), (0, 0)))
    x = x.reshape(Tw, WORD_BITS, W).transpose(2, 1, 0)          # (W, r, Tw)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (x[:, None] >> shifts[None, :, None, None]) & jnp.uint32(1)
    words = (bits << shifts[None, None, :, None]).sum(
        axis=2, dtype=jnp.uint32)                               # (W, b, Tw)
    items = words.reshape(W * WORD_BITS, Tw)[:n_items]
    row = jnp.arange(Tw * WORD_BITS).reshape(Tw, WORD_BITS)
    valid = ((row < n).astype(jnp.uint32) << shifts).sum(
        axis=1, dtype=jnp.uint32)
    return jnp.concatenate([items, valid[None]], axis=0)


def jsubset_matrix(cands: jax.Array, txns: jax.Array) -> jax.Array:
    """(C, W) x (T, W) → (C, T) bool: candidate ⊆ transaction."""
    c = cands[:, None, :]
    t = txns[None, :, :]
    return jnp.all((c & t) == c, axis=-1)
