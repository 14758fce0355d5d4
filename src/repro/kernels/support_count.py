"""Pallas TPU kernel: bitmask subset-match support counting.

This is the compute hot-spot of every MapReduce phase (the paper's ``subset()``
scan — the per-mapper pass over its transaction split).  The TPU-native design
replaces the prefix-tree walk with a dense word-parallel subset test:

    match[i, j] = AND_w ( (cand[i, w] & txn[j, w]) == cand[i, w] )
    count[i]    = sum_j match[i, j]

Tiling: candidate words are laid out transposed, ``(W, BC)`` blocks with
candidates on the 128-wide lane axis, and transactions ``(BT, W)`` with rows
on sublanes.  Each grid step builds the ``(BT, BC)`` match tile and reduces it
over sublanes into a lane-dense ``(1, BC)`` accumulator that stays resident in
the output block across the inner transaction grid axis (revisit-accumulate).
``W`` (words per bitmask, = ceil(n_items/32)) is small and static, so the word
loop fully unrolls.  Every block's last two dimensions are (8, 128) multiples
or whole array dimensions — the TPU block rule; there are no 1-D blocks.

VMEM footprint per grid step (defaults BC=256, BT=512, W≤8, uint32):
  cands 8·256·4 = 8 KiB, txns 512·128·4 = 256 KiB (W pads to one lane tile),
  match tile 512·256·4 = 512 KiB, accumulator 1 KiB → well under the scoped
  VMEM budget.  :func:`support_count_vmem` prices the blocks at any width;
  the wrappers refuse a shape over the budget (``kernels/tpu_params.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tpu_params import COMPILER_PARAMS, check_vmem, vmem_bytes

DEFAULT_BC = 256
DEFAULT_BT = 512
DEFAULT_MATMUL_BLOCK = 2048   # txn rows per dot_general chunk (jnp matmul form)


def support_count_vmem(W: int, bc: int, bt: int, matmul: bool = False) -> int:
    """VMEM bytes of :func:`support_count_pallas` (with ``matmul``, of
    :func:`support_count_matmul_pallas`) at W words and (bc, bt) blocks."""
    if matmul:
        B = -(-W // 4) * 128                 # bit planes, padded to 128 lanes
        # plus a copy of each int8 operand block that Mosaic makes for the
        # dot at most widths, and the (bt, bc) int32 product tile
        return vmem_bytes([((B, bc), 1), ((1, bc), 4), ((bt, B), 1),
                           ((1, bc), 4)],
                          scratch=[((B, bc), 1), ((bt, B), 1), ((bt, bc), 4)])
    # Mosaic keeps the unrolled word loop's (bt, 1) column slices live, each
    # padded to 128 lanes: W·bt·512 bytes, the term that bounds the width
    return vmem_bytes([((W, bc), 4), ((bt, W), 4), ((1, bc), 4)],
                      scratch=[((bt, 128 * W), 4)])


def _support_count_kernel(ct_ref, t_ref, o_ref, *, n_words: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ok = None
    for w in range(n_words):  # static unroll, W is tiny
        cw = ct_ref[w:w + 1, :]            # (1, BC)
        tw = t_ref[:, w:w + 1]             # (BT, 1)
        eq = (cw & tw) == cw               # (BT, BC)
        ok = eq if ok is None else (ok & eq)
    o_ref[...] += ok.astype(jnp.int32).sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bc", "bt", "interpret"))
def support_count_pallas(cands: jax.Array, txns: jax.Array,
                         bc: int = DEFAULT_BC, bt: int = DEFAULT_BT,
                         interpret: bool = False) -> jax.Array:
    """Support counts via the Pallas kernel.

    Shapes must be pre-padded: C % bc == 0 and T % bt == 0 (see ops.py
    wrapper); compiled for a TPU, ``bc`` is a multiple of 128 or equals C.
    """
    C, W = cands.shape
    T, Wt = txns.shape
    assert W == Wt, (W, Wt)
    assert C % bc == 0 and T % bt == 0, (C, bc, T, bt)
    check_vmem("support_count_pallas", support_count_vmem(W, bc, bt))
    out = pl.pallas_call(
        functools.partial(_support_count_kernel, n_words=W),
        grid=(C // bc, T // bt),
        in_specs=[
            pl.BlockSpec((W, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((bt, W), lambda ci, ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(cands.astype(jnp.uint32).T, txns.astype(jnp.uint32))
    return out[0]


# ---------------------------------------------------------------------------
# Matmul (bit-plane int8 dot_general) formulation — DESIGN.md §10.
#
# Containment counting as a matmul: with C_b = junpack_bits(cands) (C, B) and
# T_b = junpack_bits(txns) (T, B), B = W·32,
#
#     overlap[i, j] = Σ_b C_b[i, b] · T_b[j, b] = |cand_i ∩ txn_j|
#     match[i, j]   = overlap[i, j] == popcount(cand_i)
#     count[i]      = Σ_j match[i, j]
#
# All arithmetic is integer, so the form is bit-exact against the popcount
# impls; the dominant cost is an (C, B) × (B, T) int8 matmul the MXU/tensor
# cores were built for, instead of a VPU bitwise-op stream.
# ---------------------------------------------------------------------------

_DOT_LAST = (((1,), (1,)), ((), ()))      # contract the bit-plane axis of both


@functools.partial(jax.jit, static_argnames=("block",))
def support_count_matmul(cands: jax.Array, txns: jax.Array,
                         block: int = DEFAULT_MATMUL_BLOCK) -> jax.Array:
    """Blocked-jnp matmul twin: scan txn chunks, int8 dot_general per chunk.

    Memory: O(C · block) int32 overlap per step instead of O(C · T).
    Semantics match ``_support_count_jnp`` exactly (internal zero-pad rows
    that spuriously match empty candidates are subtracted before return).
    """
    from repro.core.bitset import jpopcount_rows, junpack_bits
    C, W = cands.shape
    cands = cands.astype(jnp.uint32)
    cb = junpack_bits(cands)                          # (C, B) int8
    widths = jpopcount_rows(cands)                    # (C,) int32
    n_pad = (-txns.shape[0]) % block
    if n_pad:
        txns = jnp.concatenate(
            [txns, jnp.zeros((n_pad, W), txns.dtype)], axis=0)
    chunks = txns.astype(jnp.uint32).reshape(-1, block, W)

    def body(acc, chunk):
        tb = junpack_bits(chunk)                      # (block, B) int8
        ov = jax.lax.dot_general(cb, tb, _DOT_LAST,
                                 preferred_element_type=jnp.int32)
        return acc + (ov == widths[:, None]).sum(axis=1, dtype=jnp.int32), None

    init = jnp.zeros((C,), jnp.int32)
    acc, _ = jax.lax.scan(body, init, chunks)
    # zero-padded txn rows overlap 0 == width 0: they match (only) empty
    # candidates — subtract, mirroring ops._empty_cand_correction
    return acc - jnp.where(widths == 0, jnp.int32(n_pad), jnp.int32(0))


def _support_count_matmul_kernel(cb_ref, w_ref, tb_ref, o_ref):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ov = jnp.dot(tb_ref[...], cb_ref[...],
                 preferred_element_type=jnp.int32)           # (BT, BC)
    o_ref[...] += (ov == w_ref[...]).astype(jnp.int32).sum(axis=0,
                                                           keepdims=True)


@functools.partial(jax.jit, static_argnames=("bc", "bt", "interpret"))
def support_count_matmul_pallas(cands: jax.Array, txns: jax.Array,
                                bc: int = DEFAULT_BC, bt: int = DEFAULT_BT,
                                interpret: bool = False) -> jax.Array:
    """Support counts via the bit-plane matmul Pallas kernel (MXU form).

    Bit planes are unpacked outside the kernel (HBM int8 matrices, the plane
    axis padded to a multiple of 128); each grid step does one
    (BT, B) × (B, BC) int8 matmul into the MXU and an equality-compare reduce
    on the VPU.  Shapes must be pre-padded: C % bc == 0 and T % bt == 0 (see
    ops.py wrapper).
    """
    from repro.core.bitset import jpopcount_rows, junpack_bits_lanes
    C, W = cands.shape
    T, Wt = txns.shape
    assert W == Wt, (W, Wt)
    assert C % bc == 0 and T % bt == 0, (C, bc, T, bt)
    check_vmem("support_count_matmul_pallas",
               support_count_vmem(W, bc, bt, matmul=True))
    cands = cands.astype(jnp.uint32)
    cb = junpack_bits_lanes(cands).T                  # (B, C) int8
    tb = junpack_bits_lanes(txns.astype(jnp.uint32))  # (T, B) int8
    widths = jpopcount_rows(cands)[None, :]           # (1, C) int32
    B = cb.shape[0]
    out = pl.pallas_call(
        _support_count_matmul_kernel,
        grid=(C // bc, T // bt),
        in_specs=[
            pl.BlockSpec((B, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((bt, B), lambda ci, ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(cb, widths, tb)
    return out[0]
