"""Support-count kernel microbenchmark + roofline terms for the counting phase.

Each counting formulation (DESIGN.md §10) is timed on its production path:
the popcount-AND subset test ("jnp"), its bit-plane int8 ``dot_general`` twin
("matmul"), and the vertical gather-scan with its membership-matmul twin.
Pallas variants are validated in interpret mode (their TPU roofline terms are
analytic).  Every timed record carries its achieved-vs-peak roofline fraction
(``count_kernel_roofline``) and each shape gets a ``count_winner`` row pairing
the measured argmin with the autotuner plan pick — the regression guard for
the C=256 vertical own-goal.  Autotuned blocks and per-impl throughput land in
``BENCH_kernels.json`` so the perf trajectory is tracked across PRs.
"""

import time

import jax
import numpy as np

import jax.numpy as jnp

from repro.core.bitset import pack_itemsets, vertical_pack
from repro.core.mapreduce import MapReduceRuntime
from repro.data import dataset_by_name
from repro.kernels import (support_count_matmul, tuned_blocks, tuned_plan,
                           vertical_count_jnp, vertical_count_matmul,
                           vertical_count_pallas)
from repro.kernels.ops import _support_count_jnp
from repro.kernels.support_count import support_count_matmul_pallas
from repro.roofline import count_kernel_roofline

from .common import emit, write_json


def _time(fn, reps=3):
    jax.block_until_ready(fn())           # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _roof(impl, *, C, T, W=1, kmax=1, n_items=None, seconds):
    """Roofline share on a chip with published peaks; a CPU run has none."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return "not measured"
    r = count_kernel_roofline(impl, C=C, T=T, W=W, kmax=kmax,
                              n_items=n_items, seconds=seconds,
                              device_kind=dev.device_kind)
    return {"bound": r["bound"], "peak_frac": r["peak_frac"]}


def _frac(roof):
    return roof["peak_frac"] if isinstance(roof, dict) else roof


def run(fast: bool = False):
    rows = []
    backend = jax.default_backend()
    record = {"backend": backend, "autotuned": {}, "kernels": {},
              "count_winner": {}}
    txns, n_items = dataset_by_name("mushroom", scale=0.25 if fast else 1.0)
    db = pack_itemsets([list(t) for t in txns], n_items)
    vdb = vertical_pack(db, n_items)
    rng = np.random.default_rng(0)
    W = db.shape[1]
    rt = MapReduceRuntime()  # only for _padded_indices
    rt._n_items = n_items

    for C in [256, 2048] if fast else [256, 2048, 16384]:
        idx = rng.integers(0, len(db), C)
        cands = db[idx]
        cand_idx = rt._padded_indices(cands)
        kmax = cand_idx.shape[1]
        T = len(db)
        timed = {}

        # horizontal jnp (XLA) popcount path, autotuned txn block
        cfg = tuned_blocks("jnp", C=C, T=T, W=W)
        cj, dj = jnp.asarray(cands), jnp.asarray(db)
        blk = min(cfg["txn_block"], T)
        wall = _time(lambda: _support_count_jnp(cj, dj, block=blk))
        timed["jnp"] = wall
        pairs = C * T
        ops = pairs * (W * 3 + 1)            # and, cmp, and-reduce, add
        name = f"kernel_support_count/C={C}/T={T}"
        record["kernels"][name] = {
            "impl": "jnp", "us": round(wall * 1e6, 1),
            "gops_cpu": round(ops / wall / 1e9, 2),
            "roofline": _roof("jnp", C=C, T=T, W=W, seconds=wall)}
        record["autotuned"][f"jnp/C={C}"] = cfg
        rows.append((name, round(wall * 1e6, 1),
                     f"pairs={pairs} gops={ops/wall/1e9:.2f}(cpu) "
                     f"frac={_frac(record['kernels'][name]['roofline'])}"))

        # horizontal bit-plane matmul twin (int8 dot_general)
        mcfg = tuned_blocks("matmul", C=C, T=T, W=W)
        mblk = min(mcfg["txn_block"], T)
        wall_m = _time(lambda: support_count_matmul(cj, dj, block=mblk))
        timed["matmul"] = wall_m
        namem = f"kernel_support_count_matmul/C={C}/T={T}"
        macs = C * T * W * 32
        record["kernels"][namem] = {
            "impl": "matmul", "us": round(wall_m * 1e6, 1),
            "gmacs_cpu": round(macs / wall_m / 1e9, 2),
            "roofline": _roof("matmul", C=C, T=T, W=W, seconds=wall_m)}
        record["autotuned"][f"matmul/C={C}"] = mcfg
        rows.append((namem, round(wall_m * 1e6, 1),
                     f"gmacs={macs/wall_m/1e9:.2f}(cpu) "
                     f"vs_jnp={wall/wall_m:.2f}x "
                     f"frac={_frac(record['kernels'][namem]['roofline'])}"))

        # vertical gather-scan (popcount) path, autotuned block
        Tw = vdb.shape[1]
        vcfg = tuned_blocks("vertical", C=C, T=Tw, W=W, kmax=kmax)
        wall_v = _time(lambda: vertical_count_jnp(vdb, cand_idx, **vcfg))
        timed["vertical"] = wall_v
        words = C * kmax * Tw
        namev = f"kernel_vertical_count/C={C}/Tw={Tw}/k={kmax}"
        record["kernels"][namev] = {
            "impl": "vertical", "us": round(wall_v * 1e6, 1),
            "block": vcfg, "gwords_cpu": round(words / wall_v / 1e9, 2),
            "roofline": _roof("vertical", C=C, T=Tw * 32, W=W, kmax=kmax,
                              n_items=n_items, seconds=wall_v)}
        record["autotuned"][f"vertical/C={C}"] = vcfg
        rows.append((namev, round(wall_v * 1e6, 1),
                     f"words={words} block={vcfg} "
                     f"speedup_vs_horizontal={wall/wall_v:.1f}x"))

        # vertical membership-matmul twin
        vmcfg = tuned_blocks("vertical_matmul", C=C, T=Tw, W=W, kmax=kmax)
        vj, ij = jnp.asarray(vdb), jnp.asarray(cand_idx)
        wall_vm = _time(lambda: vertical_count_matmul(vj, ij, **vmcfg))
        timed["vertical_matmul"] = wall_vm
        namevm = f"kernel_vertical_count_matmul/C={C}/Tw={Tw}/k={kmax}"
        record["kernels"][namevm] = {
            "impl": "vertical_matmul", "us": round(wall_vm * 1e6, 1),
            "block": vmcfg,
            "roofline": _roof("vertical_matmul", C=C, T=Tw * 32, W=W,
                              kmax=kmax, n_items=n_items, seconds=wall_vm)}
        record["autotuned"][f"vertical_matmul/C={C}"] = vmcfg
        rows.append((namevm, round(wall_vm * 1e6, 1),
                     f"vs_vertical={wall_v/wall_vm:.2f}x"))

        # per-shape winner: measured argmin + the autotuner's plan pick.
        # Plan must never be slower than the previous single-family winner
        # (the C=256 vertical own-goal this PR fixes).
        best = min(timed, key=timed.get)
        plan = tuned_plan("count", C=C, T=T, W=W, kmax=kmax)
        record["count_winner"][f"C={C}"] = {
            "measured_best": best,
            "measured_us": {k: round(v * 1e6, 1) for k, v in timed.items()},
            "plan": None if plan is None else
            {"impl": plan["impl"], "family": plan["family"]}}
        rows.append((f"count_winner/C={C}",
                     round(timed[best] * 1e6, 1),
                     f"measured_best={best} "
                     f"plan={'off' if plan is None else plan['impl']}"))

    # Pallas kernels: interpret-mode validation on a tiny slice
    Cs, ks = 64, 3
    idx_small = rt._padded_indices(db[rng.integers(0, len(db), Cs)])[:, :ks]
    ref = np.asarray(vertical_count_jnp(vdb, idx_small))
    got = np.asarray(vertical_count_pallas(vdb, idx_small, interpret=True))
    ok = bool((ref == got).all())
    record["kernels"]["vertical_pallas_interpret_valid"] = ok
    rows.append(("kernel_vertical_pallas/interpret_valid", int(ok),
                 f"C={Cs} kmax={ks} matches_jnp={ok}"))

    csmall, tsmall = jnp.asarray(db[:64]), jnp.asarray(db[:128])
    refm = np.asarray(_support_count_jnp(csmall, tsmall, block=128))
    gotm = np.asarray(support_count_matmul_pallas(csmall, tsmall, bc=32,
                                                  bt=64, interpret=True))
    okm = bool((refm == gotm).all())
    record["kernels"]["matmul_pallas_interpret_valid"] = okm
    rows.append(("kernel_matmul_pallas/interpret_valid", int(okm),
                 f"C=64 T=128 matches_jnp={okm}"))

    write_json("BENCH_kernels.json", record)
    emit(rows, ["name", "us_per_call", "derived"])
    return rows


if __name__ == "__main__":
    run()
