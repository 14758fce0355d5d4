"""Counting-side cost basis and roofline (DESIGN.md §9-§10).

* ``count_job_ops`` prices one support-counting job in the measured-ops basis
  the cost models fit against (``XFER_OPS_PER_BYTE`` folds the job's
  device→host traffic into the same basis).
* ``count_kernel_roofline`` compares a counting job's measured time with the
  chip's published peaks (``COUNT_PEAKS``): the int8 matmul peak for the
  bit-plane matmul forms, HBM bandwidth for the popcount forms.
* ``predicted_vs_achieved`` is one cost-model telemetry row.
"""

from __future__ import annotations


# -- measured-ops basis for the counting side (DESIGN.md §9) -------------------
#
# The cost-model subsystem (repro/costmodel/) fits per-(device, impl, kind)
# affine models  t ≈ a + b·ops  where ``ops`` is the job's work in this basis.
# The basis is deliberately the *horizontal* §3 form — candidate-word
# comparisons — for every impl: vertical/delta layouts do proportionally less
# word work, but the constant of proportionality is absorbed by the per-key
# slope ``b``, so the affine family is the same and fits never mix bases.
#
# Device→host transfers ride in the same basis: one PCIe byte is priced at
# ``XFER_OPS_PER_BYTE`` candidate-word comparisons (a ~10 GB/s link against a
# compute path that retires hundreds of Gops/s of word tests), so
# impl/fusion decisions see the transfer cost of the result shapes they
# produce, not only the counting work (PR 6 follow-on, DESIGN.md §10).

XFER_OPS_PER_BYTE = 64.0


def count_job_ops(n_candidates: int, n_txns: int, n_words: int = 1,
                  bytes_to_host: float = 0.0) -> float:
    """Work of one support-counting job in the measured-ops basis: C·T·W
    candidate-word comparisons (each of C candidates tested against each of
    T transactions over W mask words), plus the job's device→host result
    traffic priced at ``XFER_OPS_PER_BYTE`` ops per byte."""
    ops = float(max(int(n_candidates), 1)) * max(int(n_txns), 1) * \
        max(int(n_words), 1)
    return ops + max(float(bytes_to_host), 0.0) * XFER_OPS_PER_BYTE


# -- counting-kernel roofline (DESIGN.md §10) ----------------------------------
#
# Per-chip peaks for the achieved-vs-peak fractions, keyed by the device kind
# JAX reports (``jax.devices()[0].device_kind``).  The matmul (bit-plane
# dot) forms are compute-bound and are compared against the int8 matmul peak;
# the popcount forms stream words and are compared against HBM bandwidth.  A
# device that is not in the table has no roofline: asking for one raises.
#
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.

COUNT_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "mem_bw": 819e9},
}


def count_kernel_roofline(impl: str, *, C: int, T: int, W: int = 1,
                          kmax: int = 1, n_items: int | None = None,
                          seconds: float, device_kind: str,
                          chips: int = 1) -> dict:
    """Achieved-vs-peak terms for one counting job.

    Each counting form is priced by work its kernel cannot avoid — never
    more — so the share stays at or under 1 at the true peak:

    * the matmul forms: the ``(C, 32·W) × (32·W, T)`` bit-plane product of
      ``matmul`` / ``matmul_pallas``, the ``(C, I) × (I, T)`` membership
      product of ``vertical_matmul`` / ``vertical_matmul_pallas``, 2 int8
      ops per MAC (the kernels pad the contracted axis further);
    * ``jnp`` / ``pallas`` (horizontal popcount): the compulsory HBM traffic,
      every candidate and transaction word read once and one count written;
    * ``vertical`` (jnp gather): each candidate gathers its ``kmax`` item
      rows of ``T/32`` words;
    * ``vertical_pallas``: the kernel streams the whole ``(I+1, T/32)`` item
      array once per block of ``BC`` candidates, plus the index table; when
      the word axis fits one default block, the array is read once.

    Args:
      impl: a counting impl of ``core.mapreduce.IMPLS`` (no ``_interpret``).
      C/T/W/kmax: the job's shape (T = transaction rows, W = mask words).
      n_items: the catalog size I (vertical forms only; default 32·W).
      seconds: measured wall time of the job.
      device_kind: the chip's ``device_kind``; must be a key of
              ``COUNT_PEAKS``.
      chips: devices the job ran on — the whole job's work is compared
              against ``chips`` times the per-chip peak.

    Returns a dict with the achieved rate, the peak it is measured against,
    the ``peak_frac`` ratio, and which resource bounds the form.
    """
    if device_kind not in COUNT_PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"COUNT_PEAKS covers {sorted(COUNT_PEAKS)}")
    peaks = COUNT_PEAKS[device_kind]
    s = max(float(seconds), 1e-12)
    tw = -(-int(T) // 32)                    # transaction words
    items = 32 * W if n_items is None else n_items
    if "matmul" in impl:
        # the vertical forms contract over the I items, the horizontal over
        # the 32·W bit planes of the masks
        planes = items if impl.startswith("vertical") else 32 * W
        achieved = 2.0 * float(C) * T * planes / s
        peak, bound, unit = peaks["int8_ops"], "compute", "int8_ops_per_s"
    else:
        if impl == "vertical_pallas":
            from repro.kernels.vertical_count import BC, DEFAULT_BT
            # one word block stays resident across the candidate blocks
            bc = min(BC, -(-C // 8) * 8)
            fetches = -(-C // bc) if tw > DEFAULT_BT else 1
            words = fetches * (items + 1) * tw + C * kmax + C
        elif impl == "vertical":
            words = C * kmax * tw + C
        elif impl in ("jnp", "pallas"):
            words = W * (C + T) + C
        else:
            raise ValueError(f"no roofline model for impl {impl!r}")
        achieved = 4.0 * words / s
        peak, bound, unit = peaks["mem_bw"], "memory", "bytes_per_s"
    peak = float(peak) * chips
    return {"impl": impl, "bound": bound, "unit": unit,
            "achieved": float(achieved), "peak": peak,
            "peak_frac": float(achieved / peak)}


def predicted_vs_achieved(predicted_s: float, achieved_s: float) -> dict:
    """One predicted-vs-measured comparison row (cost-model telemetry)."""
    ratio = predicted_s / achieved_s if achieved_s > 0 else float("inf")
    rel_err = (abs(predicted_s - achieved_s) / achieved_s
               if achieved_s > 0 else float("inf"))
    return {"predicted_s": float(predicted_s), "achieved_s": float(achieved_s),
            "ratio": float(ratio), "abs_rel_err": float(rel_err)}
