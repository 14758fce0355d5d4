"""Block-size autotuner for the counting kernels.

The best tile/block sizes for the counting hot-spot depend on backend and on
the phase's shape regime (candidate rows × transaction rows/words) — exactly
the knobs the paper turns by re-sizing Hadoop input splits.  On first use per
``(backend, impl, shape-bucket)`` key the tuner times a small config sweep on
synthetic data and caches the winner:

* in-process (dict) — so a mining run tunes each bucket at most once;
* on disk (JSON at ``~/.cache/repro/autotune.json``, override with
  ``REPRO_AUTOTUNE_CACHE``) — so later processes skip the sweep entirely.

``REPRO_AUTOTUNE=0`` disables timing and returns the static defaults.
Interpret-mode Pallas (and the Pallas kernels off-TPU generally) are never
timed: interpret timings are meaningless, so defaults are returned.  The one
shape check is VMEM: a Pallas block config whose blocks do not fit the budget
at the mask width (``kernels/tpu_params.py``) is left out of the sweep, and a
plan names each family it leaves out and why.  Nothing here catches a failed
compile or run: a kernel that does not build for the device raises instead
of quietly dropping out of the sweep.

Cache format (DESIGN.md §5, §9)::

    {"cpu:cpu/vertical/C4096/T1024/W8/k5": {"block": 2048}, ...}

Keys lead with the concrete device identity (``backend:device_kind`` from
``costmodel.measure.device_key``), not just the JAX backend name — a cache
written on one TPU generation must not silently pin block sizes on another.
Legacy ``backend/...`` entries written before device-kind keying are migrated
in place: adopted under the new key on first lookup, no re-sweep.  The timing
loop itself is the shared ``costmodel.measure.time_once`` (one measurement
discipline across autotuner and cost model).

Shape buckets are next-pow2 of the padded candidate/transaction extents, so a
whole mining run touches only a handful of keys.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.costmodel.measure import device_key, time_once

DEFAULTS = {
    "jnp": {"txn_block": 4096},
    "matmul": {"txn_block": 2048},
    "pallas": {"bc": 256, "bt": 512},
    "pallas_interpret": {"bc": 256, "bt": 512},
    "matmul_pallas": {"bc": 256, "bt": 512},
    "matmul_pallas_interpret": {"bc": 256, "bt": 512},
    "vertical": {"block": 2048},
    "vertical_matmul": {"block": 2048},
    "vertical_pallas": {"bt": 512},
    "vertical_pallas_interpret": {"bt": 512},
    "vertical_matmul_pallas": {"bc": 256, "bt": 512},
    "vertical_matmul_pallas_interpret": {"bc": 256, "bt": 512},
    "rules_jnp": {"q_block": 1024},
    "rules_matmul": {"q_block": 1024},
    "rules_pallas": {"bq": 256, "br": 512},
    "rules_pallas_interpret": {"bq": 256, "br": 512},
    "rules_matmul_pallas": {"bq": 256, "br": 512},
    "rules_matmul_pallas_interpret": {"bq": 256, "br": 512},
    "delta_jnp": {"txn_block": 1024},
    "delta_matmul": {"txn_block": 1024},
    "delta_pallas": {"bc": 256, "bt": 256},
    "delta_pallas_interpret": {"bc": 256, "bt": 256},
    "delta_matmul_pallas": {"bc": 256, "bt": 256},
    "delta_matmul_pallas_interpret": {"bc": 256, "bt": 256},
}

CONFIGS = {
    "jnp": [{"txn_block": b} for b in (1024, 4096, 16384)],
    "matmul": [{"txn_block": b} for b in (512, 2048, 8192)],
    "pallas": [{"bc": bc, "bt": bt}
               for bc, bt in ((128, 512), (256, 512), (256, 1024))],
    "matmul_pallas": [{"bc": bc, "bt": bt}
                      for bc, bt in ((128, 512), (256, 512), (256, 1024))],
    "vertical": [{"block": b} for b in (512, 2048, 8192)],
    "vertical_matmul": [{"block": b} for b in (512, 2048, 8192)],
    "vertical_pallas": [{"bt": b} for b in (512, 1024, 2048)],
    "vertical_matmul_pallas": [{"bc": bc, "bt": bt}
                               for bc, bt in ((128, 512), (256, 512),
                                              (256, 1024))],
    "rules_jnp": [{"q_block": b} for b in (256, 1024, 4096)],
    "rules_matmul": [{"q_block": b} for b in (256, 1024, 4096)],
    "rules_pallas": [{"bq": bq, "br": br}
                     for bq, br in ((128, 512), (256, 512), (256, 1024))],
    "rules_matmul_pallas": [{"bq": bq, "br": br}
                            for bq, br in ((128, 512), (256, 512),
                                           (256, 1024))],
    "delta_jnp": [{"txn_block": b} for b in (256, 1024, 4096)],
    "delta_matmul": [{"txn_block": b} for b in (256, 1024, 4096)],
    "delta_pallas": [{"bc": bc, "bt": bt}
                     for bc, bt in ((128, 256), (256, 256), (256, 512))],
    "delta_matmul_pallas": [{"bc": bc, "bt": bt}
                            for bc, bt in ((128, 256), (256, 256),
                                           (256, 512))],
}

# -- cross-family plans (DESIGN.md §10) ---------------------------------------
#
# ``tuned_plan`` searches *across* implementation families (popcount vs
# matmul, horizontal vs vertical, jnp vs Pallas) at one shape bucket and
# persists the overall winner — the per-family ``tuned_blocks`` sweep only
# picks block sizes *within* a family, which is how the BENCH own-goal of a
# tuned-but-43×-slower vertical config at C=256 happened.  The jnp baseline
# family is always timed, so the recorded winner can never lose to it.

PLAN_FAMILIES = {
    "count": ("jnp", "matmul", "vertical", "vertical_matmul",
              "pallas", "matmul_pallas", "vertical_pallas",
              "vertical_matmul_pallas"),
    "delta": ("delta_jnp", "delta_matmul", "delta_pallas",
              "delta_matmul_pallas"),
    "rules": ("rules_jnp", "rules_matmul", "rules_pallas",
              "rules_matmul_pallas"),
}
PLAN_BASELINES = {"count": "jnp", "delta": "delta_jnp", "rules": "rules_jnp"}

# skip (never the baseline / predicted winner) families the calibrated cost
# model prices more than this factor above the predicted best — pure pruning
# of the timing sweep, not a substitute for measuring the finalists
PLAN_PRICE_SKIP = 8.0

# caps on the synthetic timing shapes: tuning must stay ≪ one counting job
_CAP_C = 4096
_CAP_T_ROWS = 8192     # horizontal: transaction rows
_CAP_T_WORDS = 2048    # vertical: transaction words (= 64k transactions)

# Cross-family plan sweeps time ONE config per family and persist the winner
# forever, so they can afford (nearly) true candidate extents.  Capped-shape
# timings mislead there: families scale differently past the cap (the
# vertical gather-scan is strongly sublinear in C while the horizontal path
# turns superlinear once the txn tile falls out of cache), so a C=16384 plan
# timed at C=4096 picks the wrong layout.
_PLAN_CAP_C = 16384

_memory_cache: dict = {}


def cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save_disk(store: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(store, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # cache is best-effort; in-process dict still holds the winner


def _bucket(n: int) -> int:
    """Next power of two ≥ n (≥ 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


# timing now shared with the cost model; alias kept for older callers/tests
_time_once = time_once


def _candidate_runner(impl: str, C: int, T: int, W: int, kmax: int,
                      cap_c: int = _CAP_C):
    """Build per-config callables over synthetic data of the bucketed shape."""
    rng = np.random.default_rng(0)
    if impl in ("jnp", "matmul", "pallas", "matmul_pallas"):
        C = min(C, cap_c)
        T = min(T, _CAP_T_ROWS)
        cands = jnp.asarray(rng.integers(0, 2**32, (C, W), dtype=np.uint32))
        txns = jnp.asarray(rng.integers(0, 2**32, (T, W), dtype=np.uint32))
        if impl == "jnp":
            from .ops import _support_count_jnp

            def make(cfg):
                blk = min(cfg["txn_block"], T)
                return lambda: _support_count_jnp(cands, txns, block=blk)
        elif impl == "matmul":
            from .support_count import support_count_matmul

            def make(cfg):
                blk = min(cfg["txn_block"], T)
                return lambda: support_count_matmul(cands, txns, block=blk)
        else:
            from .support_count import (support_count_matmul_pallas,
                                        support_count_pallas)
            fn = (support_count_matmul_pallas if impl == "matmul_pallas"
                  else support_count_pallas)

            def make(cfg):
                bc = min(cfg["bc"], C)
                bt = cfg["bt"]
                tp = T + ((-T) % bt)
                tx = jnp.concatenate(
                    [txns, jnp.zeros((tp - T, W), txns.dtype)], axis=0)
                return lambda: fn(cands, tx, bc=bc, bt=bt)
        return make
    if impl in ("vertical", "vertical_matmul", "vertical_pallas",
                "vertical_matmul_pallas"):
        C = min(C, cap_c)
        Tw = min(T, _CAP_T_WORDS)
        n_items = max(W * 32 - 1, 1)
        vdb = rng.integers(0, 2**32, (n_items + 1, Tw), dtype=np.uint32)
        vdb[-1] = 0xFFFFFFFF                      # valid-transaction mask row
        vdb = jnp.asarray(vdb)
        idx = np.full((C, kmax), n_items, np.int32)
        for j in range(kmax):
            idx[:, j] = rng.integers(0, n_items, C)
        idx = jnp.asarray(idx)
        if impl in ("vertical", "vertical_matmul"):
            from .vertical_count import (vertical_count_jnp,
                                         vertical_count_matmul)
            fn = (vertical_count_matmul if impl == "vertical_matmul"
                  else vertical_count_jnp)

            def make(cfg):
                return lambda: fn(vdb, idx, block=cfg["block"])
        elif impl == "vertical_pallas":
            from .vertical_count import vertical_count_pallas

            def make(cfg):
                return lambda: vertical_count_pallas(vdb, idx, bt=cfg["bt"])
        else:
            from .vertical_count import vertical_count_matmul_pallas

            def make(cfg):
                bc = min(cfg["bc"], C)
                return lambda: vertical_count_matmul_pallas(
                    vdb, idx, bc=bc, bt=cfg["bt"])
        return make
    if impl in ("delta_jnp", "delta_matmul", "delta_pallas",
                "delta_matmul_pallas"):
        C = min(C, cap_c)
        T = min(T, _CAP_T_ROWS)       # slab rows (added + evicted)
        cands = jnp.asarray(rng.integers(0, 2**32, (C, W), dtype=np.uint32))
        txns = jnp.asarray(rng.integers(0, 2**32, (T, W), dtype=np.uint32))
        signs = jnp.asarray(rng.choice(np.array([-1, 1], np.int32), T))
        if impl in ("delta_jnp", "delta_matmul"):
            from .delta_count import delta_count_jnp, delta_count_matmul
            fn = (delta_count_matmul if impl == "delta_matmul"
                  else delta_count_jnp)

            def make(cfg):
                blk = min(cfg["txn_block"], T)
                return lambda: fn(cands, txns, signs, block=blk)
        else:
            from .delta_count import (delta_count_matmul_pallas,
                                      delta_count_pallas)
            fn = (delta_count_matmul_pallas if impl == "delta_matmul_pallas"
                  else delta_count_pallas)

            def make(cfg):
                bc = min(cfg["bc"], C)
                bt = cfg["bt"]
                tp = T + ((-T) % bt)
                tx = jnp.concatenate(
                    [txns, jnp.zeros((tp - T, W), txns.dtype)], axis=0)
                sg = jnp.concatenate(
                    [signs, jnp.zeros((tp - T,), signs.dtype)])
                return lambda: fn(cands, tx, sg, bc=bc, bt=bt)
        return make
    if impl in ("rules_jnp", "rules_matmul", "rules_pallas",
                "rules_matmul_pallas"):
        R = min(C, cap_c)             # rules play the candidate role
        Q = min(T, _CAP_T_ROWS)        # baskets play the transaction role
        antes = rng.integers(0, 2**32, (R, W), dtype=np.uint32)
        cons = rng.integers(0, 2**32, (R, W), dtype=np.uint32) & ~antes
        scores = jnp.asarray(rng.random(R, dtype=np.float32))
        antes, cons = jnp.asarray(antes), jnp.asarray(cons)
        baskets = jnp.asarray(rng.integers(0, 2**32, (Q, W), dtype=np.uint32))
        if impl in ("rules_jnp", "rules_matmul"):
            from .rule_match import rule_scores_jnp, rule_scores_matmul
            fn = (rule_scores_matmul if impl == "rules_matmul"
                  else rule_scores_jnp)

            def make(cfg):
                qb = min(cfg["q_block"], Q)
                return lambda: fn(antes, cons, scores, baskets, q_block=qb)
        else:
            from .rule_match import (rule_scores_matmul_pallas,
                                     rule_scores_pallas)
            fn = (rule_scores_matmul_pallas if impl == "rules_matmul_pallas"
                  else rule_scores_pallas)

            def make(cfg):
                return lambda: fn(antes, cons, scores, baskets,
                                  bq=cfg["bq"], br=cfg["br"])
        return make
    raise ValueError(f"unknown impl {impl!r}")


def block_vmem(impl: str, cfg: dict, W: int) -> int:
    """VMEM bytes the Pallas family ``impl`` needs at block config ``cfg``
    over W-word masks — the vertical families at up to ``32·W + 1`` item
    rows; 0 for the jnp families, which hold no blocks in VMEM."""
    from .delta_count import delta_count_vmem
    from .rule_match import rule_scores_vmem
    from .support_count import support_count_vmem
    from .vertical_count import vertical_count_matmul_vmem, vertical_count_vmem
    fam = impl.removesuffix("_interpret")
    mm = "matmul" in fam
    if fam in ("pallas", "matmul_pallas"):
        return support_count_vmem(W, cfg["bc"], cfg["bt"], matmul=mm)
    if fam == "vertical_pallas":
        return vertical_count_vmem(32 * W + 1, cfg["bt"])
    if fam == "vertical_matmul_pallas":
        return vertical_count_matmul_vmem(32 * W + 1, cfg["bc"], cfg["bt"])
    if fam in ("delta_pallas", "delta_matmul_pallas"):
        return delta_count_vmem(W, cfg["bc"], cfg["bt"], matmul=mm)
    if fam in ("rules_pallas", "rules_matmul_pallas"):
        return rule_scores_vmem(W, cfg["bq"], cfg["br"], matmul=mm)
    return 0


def fitting_configs(impl: str, W: int) -> list[dict]:
    """The configs of ``CONFIGS[impl]`` whose blocks fit the VMEM budget at
    W words — the one shape check of the sweeps: most Pallas blocks hold a
    whole item or bit-plane axis, so wide catalogs leave configs out."""
    from .tpu_params import VMEM_BUDGET_BYTES
    return [cfg for cfg in CONFIGS[impl]
            if block_vmem(impl, cfg, W) <= VMEM_BUDGET_BYTES]


def defaults_fit(impl: str, W: int) -> bool:
    """Whether ``DEFAULTS[impl]`` fit the VMEM budget at W words — the
    check of the static per-backend fallbacks, which run untimed at the
    default blocks: on the TPU they take a Pallas kernel only where it fits,
    else the jnp form."""
    from .tpu_params import VMEM_BUDGET_BYTES
    return block_vmem(impl, DEFAULTS[impl], W) <= VMEM_BUDGET_BYTES


def tuned_blocks(impl: str, *, C: int, T: int, W: int = 1, kmax: int = 1,
                 backend: str | None = None) -> dict:
    """Best block config for a counting job of the given shape bucket.

    Args:
      impl: any key of ``CONFIGS`` — the popcount families ("jnp", "pallas",
            "vertical", "rules_*", "delta_*") and their bit-plane "matmul"
            twins ("matmul", "matmul_pallas", "vertical_matmul", ...).
      C:    padded candidate rows.
      T:    transaction rows (horizontal impls) or words (vertical impls).
      W:    words per bitmask (horizontal) / of the item axis (vertical).
      kmax: items per candidate (vertical impls only).

    Returns a dict of keyword block sizes for the counting call.
    """
    backend = backend or jax.default_backend()
    untunable = (
        impl not in CONFIGS
        or impl.endswith("interpret")
        or ("pallas" in impl and backend != "tpu")
        or os.environ.get("REPRO_AUTOTUNE", "1") == "0"
    )
    if untunable:
        return dict(DEFAULTS.get(impl, {}))

    shape = f"{impl}/C{_bucket(C)}/T{_bucket(T)}/W{W}/k{kmax}"
    key = f"{device_key(backend)}/{shape}"
    if key in _memory_cache:
        return dict(_memory_cache[key])
    disk = _load_disk()
    if key in disk:
        _memory_cache[key] = dict(disk[key])
        return dict(disk[key])
    legacy = f"{backend}/{shape}"      # pre-device-kind cache entries
    if legacy in disk:
        disk[key] = dict(disk.pop(legacy))
        _memory_cache[key] = dict(disk[key])
        _save_disk(disk)
        return dict(disk[key])

    # configs over the VMEM budget at this width are left out; any other
    # config that fails to compile or run is a fault and raises
    configs = fitting_configs(impl, W)
    if not configs:
        from .tpu_params import VmemError
        raise VmemError(f"{impl}: no block config of {CONFIGS[impl]} fits "
                        f"the VMEM budget at W={W} words; use a jnp impl")
    make = _candidate_runner(impl, _bucket(C), _bucket(T), W, kmax)
    best_cfg, best_t = None, float("inf")
    for cfg in configs:
        t = time_once(make(cfg))
        if t < best_t:
            best_cfg, best_t = cfg, t
    _memory_cache[key] = dict(best_cfg)
    disk[key] = dict(best_cfg)
    _save_disk(disk)
    return dict(best_cfg)


def _family_shape(kind: str, family: str, C: int, T: int):
    """Per-family (C, T) timing shape: vertical families take transaction
    *words*, everything else rows; rules' T axis is query baskets."""
    if kind == "count" and family.startswith("vertical"):
        return C, max((T + 31) // 32, 1)
    return C, T


def _strip_family(kind: str, family: str) -> str:
    """Family key → the wrapper-level impl name callers dispatch on."""
    for prefix in ("delta_", "rules_"):
        if family.startswith(prefix):
            return family[len(prefix):]
    return family


def plan_families(kind: str, W: int, backend: str) -> tuple[list, dict]:
    """The families ``tuned_plan`` times for ``kind`` at W-word masks, and
    those it leaves out by shape, each with why.

    Compiled Pallas kernels exist only on the TPU (interpret-mode timings
    are meaningless, so off the TPU the Pallas families are not candidates
    at all); on the TPU a Pallas family none of whose block configs fits
    the VMEM budget at this width is left out."""
    families, left_out = [], {}
    for f in PLAN_FAMILIES[kind]:
        if "pallas" in f and backend != "tpu":
            continue
        if not fitting_configs(f, W):
            left_out[f] = f"no block config fits the VMEM budget at W={W}"
            continue
        families.append(f)
    return families, left_out


def tuned_plan(kind: str, *, C: int, T: int, W: int = 1, kmax: int = 1,
               backend: str | None = None) -> dict | None:
    """Cross-family winner for one shape bucket (DESIGN.md §10).

    Args:
      kind: "count" (mining support counts — horizontal *and* vertical
            families compete), "delta" (streaming slabs), "rules" (serving).
      C:    candidate/rule rows.
      T:    transaction/basket *rows* (vertical families are timed at the
            equivalent word count internally).
      W:    words per bitmask.
      kmax: items per candidate (prices the vertical gather width).

    Returns ``{"impl": <wrapper impl name>, "blocks": {...}, "left_out":
    {family: why}}`` — the measured argmin over every eligible family
    (:func:`plan_families`) at its own tuned block sizes, with the
    jnp baseline always timed (the cross-check that fixes tuned-but-slower
    winners) — or None when ``REPRO_AUTOTUNE=0`` (callers fall back to their
    static per-backend default).  Winners are cached in-process and on disk
    under ``{device}/plan/...`` keys.  A calibrated cost model prunes
    families priced ≥ ``PLAN_PRICE_SKIP``× the predicted best from the sweep
    (never the baseline or the predicted winner).
    """
    if os.environ.get("REPRO_AUTOTUNE", "1") == "0":
        return None
    if kind not in PLAN_FAMILIES:
        raise ValueError(f"unknown plan kind {kind!r}; "
                         f"options: {tuple(PLAN_FAMILIES)}")
    backend = backend or jax.default_backend()
    families, left_out = plan_families(kind, W, backend)
    baseline = PLAN_BASELINES[kind]
    shape = f"plan/{kind}/C{_bucket(C)}/T{_bucket(T)}/W{W}/k{kmax}"
    key = f"{device_key(backend)}/{shape}"
    if key in _memory_cache:
        return dict(_memory_cache[key])
    disk = _load_disk()
    if key in disk:
        _memory_cache[key] = dict(disk[key])
        return dict(disk[key])

    # cost-model pruning: families the calibrated fits price far above the
    # predicted best are skipped (timing still decides among the finalists)
    from repro.costmodel.model import default_model
    from repro.roofline import count_job_ops
    predicted: dict[str, float] = {}
    mdl = default_model()
    dev = device_key(backend)
    for fam in families:
        p = mdl.predict(f"{dev}/{_strip_family(kind, fam)}/count",
                        count_job_ops(C, T, W))
        if p is not None and p > 0:
            predicted[fam] = p
    keep = set(families)
    if len(predicted) >= 2:
        pbest_fam = min(predicted, key=predicted.get)
        pbest = predicted[pbest_fam]
        keep = {f for f in families
                if f == baseline or f == pbest_fam
                or predicted.get(f, 0.0) < PLAN_PRICE_SKIP * pbest}

    timed_us: dict[str, float] = {}
    best_fam, best_blocks, best_t = None, None, float("inf")
    for fam in families:
        if fam not in keep:
            continue
        fc, ft = _family_shape(kind, fam, C, T)
        blocks = tuned_blocks(fam, C=fc, T=ft, W=W, kmax=kmax,
                              backend=backend)
        make = _candidate_runner(fam, _bucket(fc), _bucket(ft), W, kmax,
                                 cap_c=_PLAN_CAP_C)
        t = time_once(make(blocks))
        timed_us[fam] = t * 1e6
        if t < best_t:
            best_fam, best_blocks, best_t = fam, blocks, t
    plan = {"impl": _strip_family(kind, best_fam), "family": best_fam,
            "blocks": dict(best_blocks), "timed_us": timed_us,
            "left_out": left_out}
    _memory_cache[key] = dict(plan)
    disk = _load_disk()        # re-read: the sweep saved its block choices
    disk[key] = dict(plan)
    _save_disk(disk)
    return dict(plan)
