"""Pallas TPU kernel: antecedent-containment rule scoring (DESIGN.md §7).

Role-swapped reuse of the support-count subset test (§2/§3): rule antecedents
play the candidates and query baskets play the transactions, but instead of
reducing matches over the transaction axis the kernel emits the full masked
score matrix

    out[q, r] = score[r]  if ante[r] ⊆ basket[q]
                          (and, with ``exclude_contained``, cons[r] ⊄ basket[q])
                -inf      otherwise

ready for a device-side ``lax.top_k`` per query.  The consequent-containment
("nothing new to recommend") test rides in the same word loop, so novelty
filtering costs one extra AND/compare per word instead of a second pass over
the (Q, R) matrix.

Tiling mirrors ``support_count.py``: rule words are laid out transposed,
``(W, BR)`` blocks with rules on the 128-wide lane axis, the ``(1, BR)``
scores lane-dense beside them, and baskets ``(BQ, W)`` on sublanes; one
``(BQ, BR)`` float32 output tile per grid step, the word loop statically
unrolled (W is tiny).  No accumulation across grid steps — every tile is
written exactly once.  The blocked-jnp twin
(:func:`rule_scores_jnp`, the CPU production path and bit-exactness oracle)
scans basket chunks with the same select, so both paths produce identical
float32 bits.  Block sizes are autotuned via ``kernels/autotune.py`` (§5)
under the ``rules_jnp`` / ``rules_pallas`` impl keys.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu_params import COMPILER_PARAMS, check_vmem, vmem_bytes

DEFAULT_BQ = 256       # baskets per tile (sublane dim)
DEFAULT_BR = 512       # rules per tile (lane dim)
DEFAULT_Q_BLOCK = 1024  # basket chunk of the jnp scan


def rule_scores_vmem(W: int, bq: int, br: int, matmul: bool = False) -> int:
    """VMEM bytes of :func:`rule_scores_pallas` (with ``matmul``, of
    :func:`rule_scores_matmul_pallas`) at W words and (bq, br) blocks."""
    out = ((bq, br), 4)
    if matmul:
        B = -(-W // 4) * 128                 # bit planes, padded to 128 lanes
        # plus a copy of each int8 operand block that Mosaic makes for the
        # dots at most widths
        return vmem_bytes([((B, br), 1), ((1, br), 4), ((B, br), 1),
                           ((1, br), 4), ((1, br), 4), ((bq, B), 1), out],
                          scratch=[((B, br), 1), ((B, br), 1), ((bq, B), 1)])
    # the unrolled word loop's lane-padded (bq, 1) column slices stay live
    return vmem_bytes([((W, br), 4), ((W, br), 4), ((1, br), 4),
                       ((bq, W), 4), out], scratch=[((bq, 128 * W), 4)])


def _rule_scores_kernel(a_ref, c_ref, s_ref, b_ref, o_ref, *, n_words: int,
                        exclude_contained: bool):
    ok = None
    bad = None
    for w in range(n_words):  # static unroll, W is tiny
        aw = a_ref[w:w + 1, :]             # (1, BR)
        bw = b_ref[:, w:w + 1]             # (BQ, 1)
        m = (aw & bw) == aw                # (BQ, BR) antecedent ⊆ basket
        ok = m if ok is None else (ok & m)
        if exclude_contained:
            cw = c_ref[w:w + 1, :]
            mc = (cw & bw) == cw           # consequent ⊆ basket — nothing new
            bad = mc if bad is None else (bad & mc)
    if exclude_contained:
        ok = ok & jnp.logical_not(bad)
    o_ref[...] = jnp.where(ok, s_ref[...], -jnp.inf)


@functools.partial(jax.jit,
                   static_argnames=("bq", "br", "exclude_contained",
                                    "interpret"))
def rule_scores_pallas(antes: jax.Array, cons: jax.Array, scores: jax.Array,
                       baskets: jax.Array, bq: int = DEFAULT_BQ,
                       br: int = DEFAULT_BR, exclude_contained: bool = True,
                       interpret: bool = False) -> jax.Array:
    """Masked rule-score matrix via the Pallas kernel.

    Args:
      antes:   (R, W) uint32 antecedent bitmasks.
      cons:    (R, W) uint32 consequent bitmasks (read only when
               ``exclude_contained``).
      scores:  (R,) float32 rank keys (confidence·lift).
      baskets: (Q, W) uint32 query bitmasks.

    Returns: (Q, R) float32 — ``scores[r]`` where rule r fires for basket q,
    ``-inf`` elsewhere.

    Rows are padded internally: pad rules get an empty antecedent (matches
    everything) but a ``-inf`` score, and — with ``exclude_contained`` — an
    empty consequent (contained in everything), so they can never surface;
    pad baskets are sliced off before return.
    """
    R, W = antes.shape
    Q, Wb = baskets.shape
    assert W == Wb, (W, Wb)
    check_vmem("rule_scores_pallas", rule_scores_vmem(W, bq, br))
    pad_r = (-R) % br
    if pad_r:
        zrow = jnp.zeros((pad_r, W), antes.dtype)
        antes = jnp.concatenate([antes, zrow], axis=0)
        cons = jnp.concatenate([cons, zrow], axis=0)
        scores = jnp.concatenate(
            [scores, jnp.full((pad_r,), -jnp.inf, scores.dtype)])
    pad_q = (-Q) % bq
    if pad_q:
        baskets = jnp.concatenate(
            [baskets, jnp.zeros((pad_q, W), baskets.dtype)], axis=0)
    Rp, Qp = antes.shape[0], baskets.shape[0]
    out = pl.pallas_call(
        functools.partial(_rule_scores_kernel, n_words=W,
                          exclude_contained=exclude_contained),
        grid=(Qp // bq, Rp // br),
        in_specs=[
            pl.BlockSpec((W, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((W, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((1, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((bq, W), lambda qi, ri: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((bq, br), lambda qi, ri: (qi, ri)),
        out_shape=jax.ShapeDtypeStruct((Qp, Rp), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(antes.astype(jnp.uint32).T, cons.astype(jnp.uint32).T,
      scores.astype(jnp.float32)[None, :], baskets.astype(jnp.uint32))
    return out[:Q, :R]


@functools.partial(jax.jit, static_argnames=("q_block", "exclude_contained"))
def rule_scores_jnp(antes: jax.Array, cons: jax.Array, scores: jax.Array,
                    baskets: jax.Array, q_block: int = DEFAULT_Q_BLOCK,
                    exclude_contained: bool = True) -> jax.Array:
    """Blocked jnp twin of :func:`rule_scores_pallas` (bit-exact agreement).

    Scans basket chunks so peak memory is ``O(q_block · R · W)`` instead of
    ``O(Q · R · W)``.
    """
    R, W = antes.shape
    Q = baskets.shape[0]
    antes = antes.astype(jnp.uint32)
    cons = cons.astype(jnp.uint32)
    scores = scores.astype(jnp.float32)
    pad_q = (-Q) % q_block
    if pad_q:
        baskets = jnp.concatenate(
            [baskets, jnp.zeros((pad_q, W), baskets.dtype)], axis=0)
    chunks = baskets.astype(jnp.uint32).reshape(-1, q_block, W)

    def body(_, blk):                       # blk: (q_block, W)
        ok = jnp.all((antes[None, :, :] & blk[:, None, :]) == antes[None, :, :],
                     axis=-1)
        if exclude_contained:
            ok &= jnp.logical_not(jnp.all(
                (cons[None, :, :] & blk[:, None, :]) == cons[None, :, :],
                axis=-1))
        return None, jnp.where(ok, scores[None, :], -jnp.inf)

    _, out = jax.lax.scan(body, None, chunks)
    return out.reshape(-1, R)[:Q]


# ---------------------------------------------------------------------------
# Matmul (bit-plane int8 dot_general) formulation — DESIGN.md §10.
#
# Containment via the overlap identity: with B_b (Q, B) basket bit planes and
# A_b (R, B) antecedent planes, ante[r] ⊆ basket[q] iff
# Σ_b B_b[q,b]·A_b[r,b] == popcount(ante[r]).  The consequent-novelty test is
# a second matmul against the consequent planes (one fused (Q,B)×(B,2R) dot
# would also work, but two dots keep the tiny-W case readable and XLA fuses
# the compare/select either way).  Integer overlaps → float32 select bits
# identical to the popcount twins.
# ---------------------------------------------------------------------------

_DOT_LAST = (((1,), (1,)), ((), ()))      # contract the bit-plane axis of both


@functools.partial(jax.jit, static_argnames=("q_block", "exclude_contained"))
def rule_scores_matmul(antes: jax.Array, cons: jax.Array, scores: jax.Array,
                       baskets: jax.Array, q_block: int = DEFAULT_Q_BLOCK,
                       exclude_contained: bool = True) -> jax.Array:
    """Blocked-jnp matmul twin of :func:`rule_scores_jnp` (bit-exact)."""
    from repro.core.bitset import jpopcount_rows, junpack_bits
    R, W = antes.shape
    Q = baskets.shape[0]
    antes = antes.astype(jnp.uint32)
    cons = cons.astype(jnp.uint32)
    scores = scores.astype(jnp.float32)
    ab = junpack_bits(antes)                          # (R, B) int8
    aw = jpopcount_rows(antes)                        # (R,) int32
    if exclude_contained:
        cb = junpack_bits(cons)
        cw = jpopcount_rows(cons)
    pad_q = (-Q) % q_block
    if pad_q:
        baskets = jnp.concatenate(
            [baskets, jnp.zeros((pad_q, W), baskets.dtype)], axis=0)
    chunks = baskets.astype(jnp.uint32).reshape(-1, q_block, W)

    def body(_, blk):                       # blk: (q_block, W)
        bb = junpack_bits(blk)                        # (q_block, B) int8
        ov = jax.lax.dot_general(bb, ab, _DOT_LAST,
                                 preferred_element_type=jnp.int32)
        ok = ov == aw[None, :]
        if exclude_contained:
            ovc = jax.lax.dot_general(bb, cb, _DOT_LAST,
                                      preferred_element_type=jnp.int32)
            ok &= ovc != cw[None, :]
        return None, jnp.where(ok, scores[None, :], -jnp.inf)

    _, out = jax.lax.scan(body, None, chunks)
    return out.reshape(-1, R)[:Q]


def _rule_scores_matmul_kernel(a_ref, aw_ref, c_ref, cw_ref, s_ref, b_ref,
                               o_ref, *, exclude_contained: bool):
    ov = jnp.dot(b_ref[...], a_ref[...],
                 preferred_element_type=jnp.int32)           # (BQ, BR)
    ok = ov == aw_ref[...]
    if exclude_contained:
        ovc = jnp.dot(b_ref[...], c_ref[...],
                      preferred_element_type=jnp.int32)
        ok &= ovc != cw_ref[...]
    o_ref[...] = jnp.where(ok, s_ref[...], -jnp.inf)


@functools.partial(jax.jit,
                   static_argnames=("bq", "br", "exclude_contained",
                                    "interpret"))
def rule_scores_matmul_pallas(antes: jax.Array, cons: jax.Array,
                              scores: jax.Array, baskets: jax.Array,
                              bq: int = DEFAULT_BQ, br: int = DEFAULT_BR,
                              exclude_contained: bool = True,
                              interpret: bool = False) -> jax.Array:
    """Masked rule-score matrix via the bit-plane matmul Pallas kernel.

    Same pad semantics as :func:`rule_scores_pallas`: pad rules get empty
    antecedents (overlap 0 == width 0 → match everything) with ``-inf``
    scores and empty consequents (never novel under ``exclude_contained``),
    pad baskets are sliced off before return.
    """
    from repro.core.bitset import jpopcount_rows, junpack_bits_lanes
    R, W = antes.shape
    Q, Wb = baskets.shape
    assert W == Wb, (W, Wb)
    check_vmem("rule_scores_matmul_pallas",
               rule_scores_vmem(W, bq, br, matmul=True))
    pad_r = (-R) % br
    if pad_r:
        zrow = jnp.zeros((pad_r, W), antes.dtype)
        antes = jnp.concatenate([antes, zrow], axis=0)
        cons = jnp.concatenate([cons, zrow], axis=0)
        scores = jnp.concatenate(
            [scores, jnp.full((pad_r,), -jnp.inf, scores.dtype)])
    pad_q = (-Q) % bq
    if pad_q:
        baskets = jnp.concatenate(
            [baskets, jnp.zeros((pad_q, W), baskets.dtype)], axis=0)
    antes = antes.astype(jnp.uint32)
    cons = cons.astype(jnp.uint32)
    # rules on lanes: (B, R) int8 planes and (1, R) widths/scores
    ab, aw = junpack_bits_lanes(antes).T, jpopcount_rows(antes)[None, :]
    cb, cw = junpack_bits_lanes(cons).T, jpopcount_rows(cons)[None, :]
    bb = junpack_bits_lanes(baskets.astype(jnp.uint32))     # (Q, B) int8
    B = ab.shape[0]
    Rp, Qp = antes.shape[0], baskets.shape[0]
    out = pl.pallas_call(
        functools.partial(_rule_scores_matmul_kernel,
                          exclude_contained=exclude_contained),
        grid=(Qp // bq, Rp // br),
        in_specs=[
            pl.BlockSpec((B, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((1, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((B, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((1, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((1, br), lambda qi, ri: (0, ri)),
            pl.BlockSpec((bq, B), lambda qi, ri: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((bq, br), lambda qi, ri: (qi, ri)),
        out_shape=jax.ShapeDtypeStruct((Qp, Rp), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(ab, aw, cb, cw, scores.astype(jnp.float32)[None, :], bb)
    return out[:Q, :R]
