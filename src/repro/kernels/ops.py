"""Jit'd public wrappers around the Pallas kernels, with shape padding and a
memory-safe blocked-jnp path for non-TPU backends.

``support_count(cands, txns, impl=...)``
  impl="pallas"  — the Pallas kernel, compiled for the TPU (raises elsewhere).
  impl="pallas_interpret" — the same kernel in the Pallas interpreter (any
                   backend; the CPU tests' way to run it).
  impl="jnp"     — blocked pure-jnp path (XLA-vectorized; default on CPU).
  impl="matmul"  — blocked bit-plane int8 dot_general form (DESIGN.md §10;
                   the tensor-core-native formulation, default on GPU).
  impl="matmul_pallas" / "matmul_pallas_interpret" — the matmul form as a
                   Pallas MXU kernel.
  impl="auto"    — pallas on TPU (jnp where its blocks overflow VMEM),
                   matmul on GPU, else jnp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .support_count import (support_count_matmul, support_count_matmul_pallas,
                            support_count_pallas, support_count_vmem,
                            DEFAULT_BC, DEFAULT_BT)
from .tpu_params import VMEM_BUDGET_BYTES


def _backend() -> str:
    return jax.default_backend()


def pallas_interpret(impl: str) -> bool:
    """The ``interpret`` flag a Pallas impl name asks for.

    ``*_interpret`` names run the kernel in the Pallas interpreter on any
    backend; the plain names compile it for the TPU, and raise on any other
    backend rather than quietly switching to the interpreter.
    """
    if impl.endswith("_interpret"):
        return True
    backend = _backend()
    if backend != "tpu":
        raise ValueError(
            f"impl {impl!r} compiles a Pallas kernel for the TPU, but the "
            f"backend is {backend!r}; ask for {impl + '_interpret'!r} to run "
            f"it in the Pallas interpreter")
    return False


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)


def _empty_cand_correction(cands: jax.Array, n_pad_rows: int) -> jax.Array:
    """Zero-padded txn rows spuriously match EMPTY candidates — subtract them."""
    if n_pad_rows == 0:
        return jnp.zeros((cands.shape[0],), jnp.int32)
    is_empty = (cands == 0).all(axis=1)
    return jnp.where(is_empty, jnp.int32(n_pad_rows), jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("block",))
def _support_count_jnp(cands: jax.Array, txns: jax.Array, block: int = 4096) -> jax.Array:
    """Blocked jnp path: scan transaction chunks, accumulate counts.

    Memory: O(C * block) per step instead of O(C * T).
    """
    C, W = cands.shape
    n_pad = (-txns.shape[0]) % block
    txns = _pad_rows(txns, block)
    chunks = txns.reshape(-1, block, W)

    def body(acc, chunk):
        c = cands[:, None, :]
        t = chunk[None, :, :]
        match = jnp.all((c & t) == c, axis=-1)
        return acc + match.sum(axis=1).astype(jnp.int32), None

    init = jnp.zeros((C,), jnp.int32)
    acc, _ = jax.lax.scan(body, init, chunks)
    return acc - _empty_cand_correction(cands, n_pad)


def support_count(cands, txns, impl: str = "auto",
                  bc: int = DEFAULT_BC, bt: int = DEFAULT_BT) -> jax.Array:
    """Count, for each bitmask candidate, the transactions that contain it.

    Args:
      cands: (C, W) uint32 candidate bitmasks (any array-like).
      txns:  (T, W) uint32 transaction bitmasks.
      impl:  "auto" | "jnp" | "matmul" | "pallas" | "matmul_pallas" (and
             the ``*_interpret`` twins of the Pallas names).

    Returns:
      (C,) int32 support counts.

    Padding notes: rows are zero-padded to the block multiples.  A zero
    *transaction* row contains no non-empty candidate, so it never inflates a
    real candidate's count; zero *candidate* rows are sliced off before return.
    """
    cands = jnp.asarray(np.asarray(cands), dtype=jnp.uint32)
    txns = jnp.asarray(np.asarray(txns), dtype=jnp.uint32)
    C = cands.shape[0]
    if C == 0:
        return jnp.zeros((0,), jnp.int32)
    if impl == "auto":
        backend = _backend()
        impl = {"tpu": "pallas", "gpu": "matmul"}.get(backend, "jnp")
        if impl == "pallas" and (support_count_vmem(cands.shape[1], bc, bt)
                                 > VMEM_BUDGET_BYTES):
            impl = "jnp"
    if impl == "jnp":
        return _support_count_jnp(cands, txns)
    if impl == "matmul":
        return support_count_matmul(cands, txns)
    if impl in ("pallas", "matmul_pallas", "pallas_interpret",
                "matmul_pallas_interpret"):
        interpret = pallas_interpret(impl)
        n_pad = (-txns.shape[0]) % bt
        cp = _pad_rows(cands, bc)
        tp = _pad_rows(txns, bt)
        fn = (support_count_matmul_pallas if impl.startswith("matmul")
              else support_count_pallas)
        out = fn(cp, tp, bc=bc, bt=bt, interpret=interpret)[:C]
        return out - _empty_cand_correction(cands, n_pad)
    raise ValueError(f"unknown impl {impl!r}")
