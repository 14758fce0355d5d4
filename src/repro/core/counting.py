"""Device-side support counting used inside the MapReduce runtime.

These functions are traced (called inside ``jax.jit`` / ``shard_map``), so they
take pre-padded static shapes and never touch the host.  Block sizes are
decided *before* tracing by the autotuner (:mod:`repro.kernels.autotune`) and
passed in as static keywords.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.support_count import (support_count_matmul,
                                         support_count_matmul_pallas,
                                         support_count_pallas)
from repro.kernels.ops import (_empty_cand_correction, _support_count_jnp,
                               pallas_interpret)
from repro.kernels.vertical_count import (DEFAULT_BLOCK, DEFAULT_BT,
                                          vertical_count_jnp,
                                          vertical_count_matmul,
                                          vertical_count_matmul_pallas,
                                          vertical_count_pallas)


def local_counts(db_local: jax.Array, cands: jax.Array, impl: str,
                 txn_block: int = 4096, bc: int | None = None,
                 bt: int = 512) -> jax.Array:
    """Per-device support counts (the Mapper + Combiner of one split).

    Args:
      db_local: (Nd, W) uint32 — this device's transaction shard (zero-padded).
      cands:    (C, W) uint32 — candidate bitmasks (replicated, zero-padded,
                C a multiple of the kernel block).
      impl:     "pallas" | "pallas_interpret" | "jnp" | "matmul" |
                "matmul_pallas" | "matmul_pallas_interpret" (DESIGN.md §10).
      txn_block / bc / bt: block sizes (autotuned by the runtime).

    Returns: (C,) int32 local counts.
    """
    if impl == "jnp":
        block = min(txn_block, max(db_local.shape[0], 1))
        return _support_count_jnp(cands, db_local, block=block)
    if impl == "matmul":
        block = min(txn_block, max(db_local.shape[0], 1))
        return support_count_matmul(cands, db_local, block=block)
    if impl in ("pallas", "pallas_interpret", "matmul_pallas",
                "matmul_pallas_interpret"):
        bc = min(bc or 256, cands.shape[0])
        nd = db_local.shape[0]
        pad = (-nd) % bt
        if pad:
            db_local = jnp.concatenate(
                [db_local, jnp.zeros((pad, db_local.shape[1]), db_local.dtype)], axis=0)
        fn = (support_count_matmul_pallas if impl.startswith("matmul")
              else support_count_pallas)
        out = fn(cands, db_local, bc=bc, bt=bt,
                 interpret=pallas_interpret(impl))
        return out - _empty_cand_correction(cands, pad)
    raise ValueError(f"unknown impl {impl!r}")


def local_counts_vertical(vdb_local: jax.Array, cand_idx: jax.Array,
                          impl: str = "jnp", block: int = DEFAULT_BLOCK,
                          bc: int = 256, bt: int = DEFAULT_BT) -> jax.Array:
    """Vertical-layout support counting (§Perf iteration M-D).

    vdb_local: (I+1, Tw) uint32 — item-major transaction bitmaps for this
      shard; row I is the valid-transaction mask (AND identity for padding).
    cand_idx: (C, kmax) int32 — item ids per candidate, padded with I.
    impl: "jnp" (blocked gather-scan) | "pallas" | "pallas_interpret"
      (tiled popcount-AND kernel, kernels/vertical_count.py) | "matmul" |
      "matmul_pallas" | "matmul_pallas_interpret" (bit-plane membership
      matmul, DESIGN.md §10).

    count = popcount(AND of the candidate's item rows).  Work per candidate is
    O(k · N/32) words instead of the horizontal O(N · W) — the vertical data
    layout of Jen et al. ([15] in the paper), adopted as a beyond-paper
    optimization of the counting phase.
    """
    if impl in ("pallas", "pallas_interpret"):
        return vertical_count_pallas(vdb_local, cand_idx, bt=bt,
                                     interpret=pallas_interpret(impl))
    if impl in ("matmul_pallas", "matmul_pallas_interpret"):
        return vertical_count_matmul_pallas(
            vdb_local, cand_idx, bc=bc, bt=bt,
            interpret=pallas_interpret(impl))
    if impl == "matmul":
        return vertical_count_matmul(vdb_local, cand_idx, block=block)
    if impl == "jnp":
        return vertical_count_jnp(vdb_local, cand_idx, block=block)
    raise ValueError(f"unknown vertical impl {impl!r}")
