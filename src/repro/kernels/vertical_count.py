"""Pallas TPU kernel: vertical (item-major) popcount-AND support counting.

Vertical layout (DESIGN.md §3): row ``i`` of the vertical DB is the bitmap of
transactions containing item ``i``; ``support(candidate) = popcount(AND of its
item rows)``.  Work per candidate is ``O(k · N/32)`` words instead of the
horizontal ``O(N · W)`` — the vertical data layout of Jen et al. (related work
[15] of the paper).

Kernel design (replaces the gather-heavy jnp scan):

* grid ``(C // bc, Tw // bt)`` — candidate blocks × transaction-word blocks.
  Each step holds the whole item axis of one word block in VMEM, an
  ``(I+1, bt)`` tile (catalogs are small next to the transaction axis).
* the ``(bc, kmax)`` block of the candidate→row index table sits in SMEM, and
  the kernel gathers each candidate's item rows with dynamic sublane loads,
  ANDs them, and writes eight candidates at a time into a ``(bc, bt)`` VMEM
  scratch at an aligned offset.
* the popcount row sums leave lane-dense: a ``(8, bt) × (bc, bt)ᵀ`` matmul of
  ones against the popcounts (exact: every value is an integer ≤ 32) gives
  the ``(1, bc)`` output block, revisited across the word axis.
* padded candidate slots point at the valid-transaction mask row (the AND
  identity), and transaction-word padding is zeros (contributes 0 popcount),
  so no correction terms are needed.

Every block's last two dimensions are (8, 128) multiples or whole array
dimensions (the TPU block rule).  The whole item axis in VMEM bounds the
catalog: :func:`vertical_count_vmem` prices the blocks, and the wrapper
refuses a shape over the budget (``kernels/tpu_params.py``) — about 2,000
items at ``bt=512``.  The jnp fallback (`vertical_count_jnp`,
§Perf iteration M-D) remains the production path on CPU, where Pallas runs in
interpret mode for validation only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu_params import COMPILER_PARAMS, check_vmem, vmem_bytes

DEFAULT_BLOCK = 2048   # candidate block of the jnp scan fallback
DEFAULT_BT = 512       # transaction words per block (lane dim, multiple of 128)
DEFAULT_BC = 256       # candidates per block of the matmul Pallas kernel
BC = 256               # candidates per block of the popcount Pallas kernel


def vertical_count_vmem(n_rows: int, bt: int, bc: int = BC) -> int:
    """VMEM bytes of :func:`vertical_count_pallas` over ``n_rows`` (= I+1)
    item rows at ``bt`` words per block: the whole-item-axis word block, the
    output block and the ``(bc, bt)`` AND scratch."""
    return vmem_bytes([((n_rows, bt), 4), ((1, bc), 4)],
                      scratch=[((bc, bt), 4)])


def vertical_count_matmul_vmem(n_rows: int, bc: int, bt: int) -> int:
    """VMEM bytes of :func:`vertical_count_matmul_pallas` over ``n_rows``
    (= I+1) item rows at (bc, bt) blocks."""
    ip = -(-n_rows // 128) * 128             # item axis, padded to 128 lanes
    # plus a copy of each int8 operand block that Mosaic makes for the dot,
    # and the (bt, bc) int32 product tile
    return vmem_bytes([((bt, ip), 1), ((ip, bc), 1), ((1, bc), 4),
                       ((1, bc), 4)],
                      scratch=[((bt, ip), 1), ((ip, bc), 1), ((bt, bc), 4)])


def _vertical_count_kernel(idx_ref, v_ref, o_ref, acc_ref, *, kmax: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def group(g, carry):                   # eight candidates per aligned store
        rows = []
        for r in range(8):
            c = g * 8 + r
            row = v_ref[pl.ds(idx_ref[c, 0], 1), :]          # (1, BT)
            for j in range(1, kmax):
                row = row & v_ref[pl.ds(idx_ref[c, j], 1), :]
            rows.append(row)
        acc_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :] = jnp.concatenate(
            rows, axis=0)
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[0] // 8, group, 0)
    pc = jax.lax.population_count(acc_ref[...]).astype(
        jnp.int32).astype(jnp.float32)
    ones = jnp.ones((8, pc.shape[1]), jnp.float32)
    sums = jax.lax.dot_general(ones, pc, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (8, BC)
    o_ref[...] += sums[0:1].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def vertical_count_pallas(vdb: jax.Array, cand_idx: jax.Array,
                          bt: int = DEFAULT_BT,
                          interpret: bool = False) -> jax.Array:
    """Support counts from the vertical layout via the Pallas kernel.

    Args:
      vdb:      (I+1, Tw) uint32 item-major bitmaps; row I is the
                valid-transaction mask (AND identity used for padded slots).
      cand_idx: (C, kmax) int32 item ids per candidate, padded with I.
      bt:       transaction words per block (clamped to the padded Tw).

    Candidates go ``BC`` to a block (fewer, rounded up to 8, when C is
    smaller).

    Returns: (C,) int32 counts.
    """
    C, kmax = cand_idx.shape
    I1, tw = vdb.shape
    # Clamp the blocks so tiny inputs don't pad to a full default block, then
    # pad candidates with sentinel rows and words with zeros to the multiples.
    bc = min(BC, max(((C + 7) // 8) * 8, 8))
    bt = min(bt, max(((tw + 127) // 128) * 128, 128))
    check_vmem("vertical_count_pallas", vertical_count_vmem(I1, bt, bc))
    pad_c, pad_t = (-C) % bc, (-tw) % bt
    cand_idx = jnp.pad(cand_idx.astype(jnp.int32), ((0, pad_c), (0, 0)),
                       constant_values=I1 - 1)
    vdb = jnp.pad(vdb.astype(jnp.uint32), ((0, 0), (0, pad_t)))
    Cp = cand_idx.shape[0]
    out = pl.pallas_call(
        functools.partial(_vertical_count_kernel, kmax=kmax),
        grid=(Cp // bc, vdb.shape[1] // bt),
        in_specs=[
            pl.BlockSpec((bc, kmax), lambda ci, ti: (ci, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((I1, bt), lambda ci, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, Cp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bc, bt), jnp.uint32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(cand_idx, vdb)
    return out[0, :C]


def vertical_count_jnp(vdb: jax.Array, cand_idx: jax.Array,
                       block: int = DEFAULT_BLOCK) -> jax.Array:
    """Blocked jnp oracle/fallback: gather item rows, AND, popcount.

    Scans candidate blocks so peak memory is ``O(block · kmax · Tw)``.
    """
    vdb = jnp.asarray(vdb)          # host arrays are fine (oracle/bench use)
    cand_idx = jnp.asarray(cand_idx)
    C, kmax = cand_idx.shape
    pad = (-C) % block
    if pad:
        cand_idx = jnp.concatenate(
            [cand_idx, jnp.full((pad, kmax), vdb.shape[0] - 1,
                                cand_idx.dtype)], axis=0)
    blocks = cand_idx.reshape(-1, block, kmax)

    def body(_, idx_blk):
        rows = vdb[idx_blk]                          # (block, kmax, Tw)
        acc = rows[:, 0]
        for j in range(1, kmax):                     # kmax tiny: unrolled ANDs
            acc = acc & rows[:, j]
        cnt = jax.lax.population_count(acc).astype(jnp.int32).sum(-1)
        return None, cnt

    _, counts = jax.lax.scan(body, None, blocks)
    return counts.reshape(-1)[:C]


# ---------------------------------------------------------------------------
# Matmul (bit-plane int8 dot_general) formulation — DESIGN.md §10.
#
# The candidate→item index table becomes a 0/1 membership matrix A (C, I)
# (scatter; duplicate slots collapse, matching the AND semantics of the
# popcount form), the vertical DB a bit matrix V (I, Tn); then
#
#     present[c, t] = Σ_i A[c, i] · V[i, t]         (one int8 matmul)
#     match[c, t]   = present[c, t] == Σ_i A[c, i]  ∧  valid[t]
#     count[c]      = Σ_t match[c, t]
#
# Sentinel-padded slots never enter A, so the valid-transaction row plays the
# same role as in the popcount form (padded txn columns are all-zero and the
# empty candidate counts exactly the valid transactions).
# ---------------------------------------------------------------------------


def _vertical_membership(idx_blk: jax.Array, n_items: int):
    """(block, kmax) ids (sentinel = n_items) → 0/1 (block, I) int8 + per-row
    distinct-item counts (block,) int32."""
    blk = idx_blk.shape[0]
    A = jnp.zeros((blk, n_items + 1), jnp.int8).at[
        jnp.arange(blk)[:, None], idx_blk].set(1)
    A = A[:, :n_items]                               # drop the sentinel column
    return A, A.sum(axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block",))
def vertical_count_matmul(vdb: jax.Array, cand_idx: jax.Array,
                          block: int = DEFAULT_BLOCK) -> jax.Array:
    """Blocked-jnp matmul twin of :func:`vertical_count_jnp` (bit-exact)."""
    from repro.core.bitset import junpack_bits
    vdb = jnp.asarray(vdb)
    cand_idx = jnp.asarray(cand_idx)
    I1, _ = vdb.shape
    n_items = I1 - 1
    C, kmax = cand_idx.shape
    vbits = junpack_bits(vdb)                        # (I+1, Tn) int8
    item_bits = vbits[:n_items]
    valid = vbits[n_items] > 0                       # (Tn,) bool
    pad = (-C) % block
    if pad:
        cand_idx = jnp.concatenate(
            [cand_idx, jnp.full((pad, kmax), n_items, cand_idx.dtype)], axis=0)
    blocks = cand_idx.reshape(-1, block, kmax)

    def body(_, idx_blk):
        A, nreal = _vertical_membership(idx_blk, n_items)
        ov = jax.lax.dot_general(A, item_bits, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)
        match = (ov == nreal[:, None]) & valid[None, :]
        return None, match.sum(axis=1, dtype=jnp.int32)

    _, counts = jax.lax.scan(body, None, blocks)
    return counts.reshape(-1)[:C]


def _vertical_matmul_kernel(v_ref, a_ref, n_ref, o_ref):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ov = jnp.dot(v_ref[...], a_ref[...],
                 preferred_element_type=jnp.int32)           # (BT, BC)
    o_ref[...] += (ov == n_ref[...]).astype(jnp.int32).sum(axis=0,
                                                           keepdims=True)


@functools.partial(jax.jit, static_argnames=("bc", "bt", "interpret"))
def vertical_count_matmul_pallas(vdb: jax.Array, cand_idx: jax.Array,
                                 bc: int = DEFAULT_BC, bt: int = DEFAULT_BT,
                                 interpret: bool = False) -> jax.Array:
    """Vertical matmul counting as a Pallas kernel: (BT, I) × (I, BC) int8
    matmuls on the MXU, candidates tiled over the grid's first axis and
    transaction columns over the second (the item axis stays whole — catalogs
    are small next to the transaction axis).

    The valid-transaction row joins every candidate as one more item, so a
    column matches only where all the candidate's items *and* the valid bit
    are set — no separate validity vector.  The item axis is zero-padded to a
    multiple of 128 (the int8 lane tiling)."""
    from repro.core.bitset import junpack_bits
    vdb = jnp.asarray(vdb)
    cand_idx = jnp.asarray(cand_idx)
    I1, _ = vdb.shape
    n_items = I1 - 1
    C, _ = cand_idx.shape
    bc = min(bc, max(((C + 127) // 128) * 128, 128))
    check_vmem("vertical_count_matmul_pallas",
               vertical_count_matmul_vmem(I1, bc, bt))
    A, nreal = _vertical_membership(cand_idx, n_items)
    A = jnp.concatenate([A, jnp.ones((C, 1), A.dtype)], axis=1)   # + valid
    nreal = nreal + 1
    pad_c = (-C) % bc
    pad_i = (-I1) % 128
    # a padded candidate row would count every valid txn (empty-set
    # semantics); poison its width so it never matches instead
    A = jnp.pad(A, ((0, pad_c), (0, pad_i))).T                 # (Ip, Cp)
    nreal = jnp.pad(nreal, (0, pad_c), constant_values=-1)[None, :]
    vbits = junpack_bits(vdb).T                                # (Tn, I+1)
    pad_t = (-vbits.shape[0]) % bt
    vbits = jnp.pad(vbits, ((0, pad_t), (0, pad_i)))           # (Tnp, Ip)
    Ip, Cp = A.shape
    out = pl.pallas_call(
        _vertical_matmul_kernel,
        grid=(Cp // bc, vbits.shape[0] // bt),
        in_specs=[
            pl.BlockSpec((bt, Ip), lambda ci, ti: (ti, 0)),
            pl.BlockSpec((Ip, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        ],
        out_specs=pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, Cp), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(vbits, A, nreal)
    return out[0, :C]
