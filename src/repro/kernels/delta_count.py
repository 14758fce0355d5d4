"""Pallas TPU kernel: signed delta support counting for streaming windows.

Streaming updates (DESIGN.md §8) change a transaction window by a micro-batch
of *added* and *evicted* transactions.  Support counts are sums over
transactions, so the new count of every tracked candidate is

    count'[i] = count[i] + |{t ∈ added : c_i ⊆ t}| − |{t ∈ evicted : c_i ⊆ t}|

and a window update only has to scan the O(delta) slab instead of the
O(window) database.  Both slabs are processed in one pass: transactions are
concatenated into a single ``(T, W)`` slab with a per-row sign vector
(+1 added, −1 evicted, 0 padding), and the kernel accumulates

    delta[i] = Σ_j sign[j] · [cand[i] ⊆ txn[j]]

Tiling mirrors ``support_count.py``: candidate words ``(W, BC)`` with
candidates on lanes × slab ``(BT, W)`` tiles in VMEM, the word loop statically
unrolled, a lane-dense ``(1, BC)`` int32 accumulator revisited across the
slab grid axis.  The signs enter as one ``(8, T)`` float32 sublane tile (row 0
the signs, rows 1–7 zero), and the signed reduction over the slab rows is a
``(8, BT) × (BT, BC)`` matmul — exact, since every product is 0 or ±1.
Sign-0 padding makes the kernel self-correcting: zero-padded slab rows match
empty (zero-padded) candidate rows, but contribute 0 — so unlike
``support_count`` no empty-candidate correction term is needed on either
path.

The blocked-jnp twin (:func:`delta_count_jnp`) is bit-exact (integer
arithmetic only) and is the CPU production path; block sizes are autotuned
via ``kernels/autotune.py`` (§5) under the ``delta_jnp`` / ``delta_pallas``
impl keys.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.autotune import (DEFAULTS, _bucket, defaults_fit,
                                    tuned_blocks, tuned_plan)
from repro.kernels.ops import pallas_interpret
from repro.kernels.tpu_params import COMPILER_PARAMS, check_vmem, vmem_bytes

DEFAULT_BC = 256
DEFAULT_BT = 256
DEFAULT_TXN_BLOCK = 1024

DELTA_IMPLS = ("auto", "jnp", "pallas", "pallas_interpret", "matmul",
               "matmul_pallas", "matmul_pallas_interpret")

MIN_SLAB_BUCKET = 32       # pow2 slab padding floor — few compiled shapes


def delta_count_vmem(W: int, bc: int, bt: int, matmul: bool = False) -> int:
    """VMEM bytes of :func:`delta_count_pallas` (with ``matmul``, of
    :func:`delta_count_matmul_pallas`) at W words and (bc, bt) blocks."""
    if matmul:
        B = -(-W // 4) * 128                 # bit planes, padded to 128 lanes
        # plus a copy of each int8 operand block that Mosaic makes for the
        # dot at most widths, and the (bt, bc) int32 product tile
        return vmem_bytes([((B, bc), 1), ((1, bc), 4), ((bt, B), 1),
                           ((8, bt), 4), ((1, bc), 4)],
                          scratch=[((B, bc), 1), ((bt, B), 1), ((bt, bc), 4)])
    # the unrolled word loop's lane-padded (bt, 1) column slices stay live
    return vmem_bytes([((W, bc), 4), ((bt, W), 4), ((8, bt), 4),
                       ((1, bc), 4)], scratch=[((bt, 128 * W), 4)])


def _sign_rows(signs: jax.Array) -> jax.Array:
    """(T,) signs → (8, T) float32: row 0 the signs, rows 1–7 zero."""
    return jnp.pad(signs.astype(jnp.float32)[None, :], ((0, 7), (0, 0)))


def _signed_sum(s_ref, ok) -> jax.Array:
    """Σ_j sign[j]·ok[j, c] over the (BT, BC) match tile → (1, BC) int32."""
    signed = jnp.dot(s_ref[...], ok.astype(jnp.float32),
                     preferred_element_type=jnp.float32)    # (8, BC)
    return signed[0:1].astype(jnp.int32)


def _delta_count_kernel(ct_ref, t_ref, s_ref, o_ref, *, n_words: int):
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
    o_ref[...] += _signed_sum(s_ref, ok)


@functools.partial(jax.jit, static_argnames=("bc", "bt", "interpret"))
def delta_count_pallas(cands: jax.Array, txns: jax.Array, signs: jax.Array,
                       bc: int = DEFAULT_BC, bt: int = DEFAULT_BT,
                       interpret: bool = False) -> jax.Array:
    """Signed delta counts via the Pallas kernel.

    Args:
      cands: (C, W) uint32 candidate bitmasks, C % bc == 0 (pre-padded).
      txns:  (T, W) uint32 slab bitmasks, T % bt == 0 (pre-padded).
      signs: (T,) int32 per-row sign: +1 added, −1 evicted, 0 padding.

    Returns: (C,) int32 signed count deltas.
    """
    C, W = cands.shape
    T, Wt = txns.shape
    assert W == Wt, (W, Wt)
    assert C % bc == 0 and T % bt == 0, (C, bc, T, bt)
    check_vmem("delta_count_pallas", delta_count_vmem(W, bc, bt))
    out = pl.pallas_call(
        functools.partial(_delta_count_kernel, n_words=W),
        grid=(C // bc, T // bt),
        in_specs=[
            pl.BlockSpec((W, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((bt, W), lambda ci, ti: (ti, 0)),
            pl.BlockSpec((8, bt), lambda ci, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(cands.astype(jnp.uint32).T, txns.astype(jnp.uint32), _sign_rows(signs))
    return out[0]


@functools.partial(jax.jit, static_argnames=("block",))
def delta_count_jnp(cands: jax.Array, txns: jax.Array, signs: jax.Array,
                    block: int = DEFAULT_TXN_BLOCK) -> jax.Array:
    """Blocked jnp twin of :func:`delta_count_pallas` (bit-exact: int math).

    Scans slab chunks so peak memory is O(C · block) instead of O(C · T).
    """
    C, W = cands.shape
    pad = (-txns.shape[0]) % block
    if pad:
        txns = jnp.concatenate(
            [txns, jnp.zeros((pad, W), txns.dtype)], axis=0)
        signs = jnp.concatenate([signs, jnp.zeros((pad,), signs.dtype)])
    chunks = txns.reshape(-1, block, W)
    sign_chunks = signs.astype(jnp.int32).reshape(-1, block)

    def body(acc, xs):
        chunk, sgn = xs
        c = cands[:, None, :]
        t = chunk[None, :, :]
        match = jnp.all((c & t) == c, axis=-1)
        signed = jnp.where(match, sgn[None, :], jnp.int32(0))
        return acc + signed.sum(axis=1).astype(jnp.int32), None

    init = jnp.zeros((C,), jnp.int32)
    acc, _ = jax.lax.scan(body, init, (chunks, sign_chunks))
    return acc


# ---------------------------------------------------------------------------
# Matmul (bit-plane int8 dot_general) formulation — DESIGN.md §10.
#
# Same identity as support_count's matmul form, with the per-row sign folded
# into the reduction:  delta[i] = Σ_j sign[j] · [overlap[i,j] == width[i]].
# Sign-0 padding keeps the form self-correcting (zero slab rows match empty
# candidates but contribute 0), so like the popcount form no empty-candidate
# correction is needed.
# ---------------------------------------------------------------------------

_DOT_LAST = (((1,), (1,)), ((), ()))      # contract the bit-plane axis of both


@functools.partial(jax.jit, static_argnames=("block",))
def delta_count_matmul(cands: jax.Array, txns: jax.Array, signs: jax.Array,
                       block: int = DEFAULT_TXN_BLOCK) -> jax.Array:
    """Blocked-jnp matmul twin of :func:`delta_count_jnp` (bit-exact)."""
    from repro.core.bitset import jpopcount_rows, junpack_bits
    C, W = cands.shape
    cands = cands.astype(jnp.uint32)
    cb = junpack_bits(cands)                          # (C, B) int8
    widths = jpopcount_rows(cands)                    # (C,) int32
    pad = (-txns.shape[0]) % block
    if pad:
        txns = jnp.concatenate(
            [txns, jnp.zeros((pad, W), txns.dtype)], axis=0)
        signs = jnp.concatenate([signs, jnp.zeros((pad,), signs.dtype)])
    chunks = txns.astype(jnp.uint32).reshape(-1, block, W)
    sign_chunks = signs.astype(jnp.int32).reshape(-1, block)

    def body(acc, xs):
        chunk, sgn = xs
        tb = junpack_bits(chunk)                      # (block, B) int8
        ov = jax.lax.dot_general(cb, tb, _DOT_LAST,
                                 preferred_element_type=jnp.int32)
        signed = jnp.where(ov == widths[:, None], sgn[None, :], jnp.int32(0))
        return acc + signed.sum(axis=1).astype(jnp.int32), None

    init = jnp.zeros((C,), jnp.int32)
    acc, _ = jax.lax.scan(body, init, (chunks, sign_chunks))
    return acc


def _delta_count_matmul_kernel(cb_ref, w_ref, tb_ref, s_ref, o_ref):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ov = jnp.dot(tb_ref[...], cb_ref[...],
                 preferred_element_type=jnp.int32)           # (BT, BC)
    o_ref[...] += _signed_sum(s_ref, ov == w_ref[...])


@functools.partial(jax.jit, static_argnames=("bc", "bt", "interpret"))
def delta_count_matmul_pallas(cands: jax.Array, txns: jax.Array,
                              signs: jax.Array, bc: int = DEFAULT_BC,
                              bt: int = DEFAULT_BT,
                              interpret: bool = False) -> jax.Array:
    """Signed delta counts via the bit-plane matmul Pallas kernel (MXU form).

    Same pre-padding contract as :func:`delta_count_pallas`.
    """
    from repro.core.bitset import jpopcount_rows, junpack_bits_lanes
    C, W = cands.shape
    T, Wt = txns.shape
    assert W == Wt, (W, Wt)
    assert C % bc == 0 and T % bt == 0, (C, bc, T, bt)
    check_vmem("delta_count_matmul_pallas",
               delta_count_vmem(W, bc, bt, matmul=True))
    cands = cands.astype(jnp.uint32)
    cb = junpack_bits_lanes(cands).T                  # (B, C) int8
    tb = junpack_bits_lanes(txns.astype(jnp.uint32))  # (T, B) int8
    widths = jpopcount_rows(cands)[None, :]           # (1, C) int32
    B = cb.shape[0]
    out = pl.pallas_call(
        _delta_count_matmul_kernel,
        grid=(C // bc, T // bt),
        in_specs=[
            pl.BlockSpec((B, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
            pl.BlockSpec((bt, B), lambda ci, ti: (ti, 0)),
            pl.BlockSpec((8, bt), lambda ci, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((1, bc), lambda ci, ti: (0, ci)),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(cb, widths, tb, _sign_rows(signs))
    return out[0]


def build_slab(added: np.ndarray, evicted: np.ndarray,
               min_bucket: int = MIN_SLAB_BUCKET):
    """Concatenate add/evict slabs, pad rows to a pow2 bucket with sign 0.

    Returns ``(slab (Tp, W) uint32, signs (Tp,) int32)`` — pow2-bucketed so
    the streaming loop touches a handful of compiled slab shapes (§2).
    """
    added = np.asarray(added, np.uint32)
    evicted = np.asarray(evicted, np.uint32)
    W = added.shape[1] if added.ndim == 2 else evicted.shape[1]
    slab = np.concatenate([added, evicted], axis=0)
    signs = np.concatenate([np.ones(added.shape[0], np.int32),
                            -np.ones(evicted.shape[0], np.int32)])
    tp = max(min_bucket, _bucket(max(slab.shape[0], 1)))
    if tp != slab.shape[0]:
        slab = np.concatenate(
            [slab, np.zeros((tp - slab.shape[0], W), np.uint32)], axis=0)
        signs = np.concatenate(
            [signs, np.zeros(tp - signs.shape[0], np.int32)])
    return slab, signs


def delta_count(cands, added, evicted, impl: str = "auto",
                autotune: bool = True) -> np.ndarray:
    """Host wrapper: signed count delta per candidate for one window update.

    Args:
      cands:   (C, W) uint32 tracked candidate bitmasks (any row count —
               pre-bucket-padding them via ``phases.bucket_pad`` keeps the
               compiled-shape set small across a stream).
      added:   (A, W) uint32 transactions entering the window.
      evicted: (E, W) uint32 transactions leaving the window.
      impl:    one of ``DELTA_IMPLS`` ("auto": the autotuned cross-family
               plan winner when autotune is on, else pallas on TPU / jnp
               elsewhere; plain "*pallas" names raise off the TPU — the
               "*_interpret" names run the kernels in the interpreter).

    Returns: (C,) int32 — add to the tracked int64 counts.
    """
    if impl not in DELTA_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; options: {DELTA_IMPLS}")
    cands = np.asarray(cands, np.uint32)
    C, W = cands.shape
    if C == 0:
        return np.zeros((0,), np.int32)
    slab, signs = build_slab(added, evicted)
    if not signs.any():
        return np.zeros((C,), np.int32)
    backend = jax.default_backend()
    T = slab.shape[0]
    if impl == "auto":
        plan = tuned_plan("delta", C=C, T=T, W=W) if autotune else None
        if plan is not None:
            impl = plan["impl"]
        else:
            impl = ("pallas" if backend == "tpu"
                    and defaults_fit("delta_pallas", W) else "jnp")
    if impl in ("jnp", "matmul"):
        key = f"delta_{impl}"
        blocks = (tuned_blocks(key, C=C, T=T, W=W) if autotune
                  else dict(DEFAULTS[key]))
        block = min(blocks["txn_block"], T)
        fn = delta_count_jnp if impl == "jnp" else delta_count_matmul
        out = fn(jnp.asarray(cands), jnp.asarray(slab),
                 jnp.asarray(signs), block=block)
        return np.asarray(out)
    matmul = impl.startswith("matmul")
    interpret = pallas_interpret(impl)
    base = "delta_matmul_pallas" if matmul else "delta_pallas"
    impl_key = f"{base}_interpret" if interpret else base
    blocks = (tuned_blocks(impl_key, C=C, T=T, W=W) if autotune
              else dict(DEFAULTS[impl_key]))
    bc = min(blocks["bc"], _bucket(C))
    bt = min(blocks["bt"], T)
    pad_c = (-C) % bc
    if pad_c:
        cands = np.concatenate(
            [cands, np.zeros((pad_c, W), np.uint32)], axis=0)
    pad_t = (-T) % bt
    if pad_t:
        slab = np.concatenate(
            [slab, np.zeros((pad_t, W), np.uint32)], axis=0)
        signs = np.concatenate([signs, np.zeros(pad_t, np.int32)])
    fn = delta_count_matmul_pallas if matmul else delta_count_pallas
    out = fn(jnp.asarray(cands), jnp.asarray(slab),
             jnp.asarray(signs), bc=bc, bt=bt, interpret=interpret)
    return np.asarray(out)[:C]
