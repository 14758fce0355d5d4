"""Cross-family autotune plans (DESIGN.md §10): winner selection + caching.

The fake-timer tests script per-family wall times into ``time_once`` so the
joint sweep's behaviour is checked deterministically — in particular the
regression this PR fixes: a tuned single-family winner ("vertical" at C=256)
that loses to the plain jnp baseline by ~43× must never be picked once the
baseline is cross-checked in the same sweep.
"""

import json

import numpy as np
import pytest

import repro.kernels.autotune as at
from repro.costmodel.measure import device_key


def _fresh(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.setattr(at, "_memory_cache", {})
    # the plan sweep prices candidates off the shared cost model; a
    # calibrated per-machine cache (~/.cache/repro/costmodel.json) could
    # prune scripted families, so isolate it too
    import repro.costmodel.model as cm
    monkeypatch.setenv("REPRO_COSTMODEL_CACHE", str(tmp_path / "cm.json"))
    monkeypatch.setattr(cm, "_default", None)


def _script_times(monkeypatch, times_us):
    """Make every family run at its scripted time (µs), configs tie."""
    def fake_runner(impl, C, T, W, kmax, **kw):
        return lambda cfg, impl=impl: impl
    def fake_time_once(marker):
        return times_us[marker] * 1e-6
    monkeypatch.setattr(at, "_candidate_runner", fake_runner)
    monkeypatch.setattr(at, "time_once", fake_time_once)


def test_plan_disabled_returns_none(monkeypatch, tmp_path):
    _fresh(monkeypatch, tmp_path)
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert at.tuned_plan("count", C=256, T=8124, W=4) is None


def test_plan_unknown_kind_raises(monkeypatch, tmp_path):
    _fresh(monkeypatch, tmp_path)
    with pytest.raises(ValueError):
        at.tuned_plan("frobnicate", C=1, T=1)


def test_plan_baseline_beats_tuned_vertical_own_goal(monkeypatch, tmp_path):
    """The recorded C=256 own-goal: vertical 107.7ms vs jnp 2.5ms — the joint
    sweep must pick jnp even though vertical was the tuned layout winner."""
    _fresh(monkeypatch, tmp_path)
    _script_times(monkeypatch, {
        "jnp": 2509.0, "matmul": 6000.0,
        "vertical": 107708.7, "vertical_matmul": 15000.0})
    plan = at.tuned_plan("count", C=256, T=8124, W=4, kmax=23, backend="cpu")
    assert plan["impl"] == "jnp" and plan["family"] == "jnp"
    assert "jnp" in plan["timed_us"]            # baseline always cross-checked
    # winner never slower than any timed family
    assert plan["timed_us"][plan["family"]] == min(plan["timed_us"].values())


@pytest.mark.parametrize("kind,times,want", [
    ("count", {"jnp": 90.0, "matmul": 20.0, "vertical": 400.0,
               "vertical_matmul": 100.0}, "matmul"),
    ("delta", {"delta_jnp": 50.0, "delta_matmul": 10.0}, "matmul"),
    ("rules", {"rules_jnp": 30.0, "rules_matmul": 5.0}, "matmul"),
])
def test_plan_picks_fastest_family(monkeypatch, tmp_path, kind, times, want):
    _fresh(monkeypatch, tmp_path)
    _script_times(monkeypatch, times)
    plan = at.tuned_plan(kind, C=128, T=1024, W=2, backend="cpu")
    assert plan["impl"] == want
    assert set(plan["timed_us"]) == set(times)


def test_plan_cached_no_resweep(monkeypatch, tmp_path):
    _fresh(monkeypatch, tmp_path)
    _script_times(monkeypatch, {"delta_jnp": 5.0, "delta_matmul": 50.0})
    first = at.tuned_plan("delta", C=64, T=512, W=1, backend="cpu")
    assert first["impl"] == "jnp"
    disk = json.load(open(tmp_path / "at.json"))
    plan_keys = [k for k in disk if "/plan/delta/" in k]
    assert len(plan_keys) == 1 and plan_keys[0].startswith(device_key("cpu"))

    def boom(*a, **kw):
        raise AssertionError("cached plan must not re-sweep")
    monkeypatch.setattr(at, "time_once", boom)
    again = at.tuned_plan("delta", C=64, T=512, W=1, backend="cpu")
    assert again["impl"] == first["impl"]
    # and a fresh process (cold memory cache) reads the disk entry
    monkeypatch.setattr(at, "_memory_cache", {})
    cold = at.tuned_plan("delta", C=64, T=512, W=1, backend="cpu")
    assert cold["impl"] == first["impl"]


def test_plan_survives_family_failures(monkeypatch, tmp_path):
    """A family whose kernel fails to build is a fault, not a quiet drop-out:
    the sweep raises, and no plan (which would hide the failure) is cached."""
    _fresh(monkeypatch, tmp_path)
    def fake_runner(impl, C, T, W, kmax, **kw):
        return lambda cfg, impl=impl: impl
    def flaky(marker):
        if marker != "delta_matmul":
            raise RuntimeError("no lowering")
        return 1e-3
    monkeypatch.setattr(at, "_candidate_runner", fake_runner)
    monkeypatch.setattr(at, "time_once", flaky)
    with pytest.raises(RuntimeError, match="no lowering"):
        at.tuned_plan("delta", C=64, T=512, W=1, backend="cpu")
    assert not at._memory_cache
    assert not (tmp_path / "at.json").exists() or not any(
        "/plan/" in k for k in json.load(open(tmp_path / "at.json")))


def test_runtime_auto_impl_follows_plan(monkeypatch, tmp_path):
    """MapReduceRuntime(impl='auto') adopts the plan winner in scatter_db."""
    from repro.core.mapreduce import IMPLS, MapReduceRuntime
    _fresh(monkeypatch, tmp_path)
    _script_times(monkeypatch, {
        "jnp": 500.0, "matmul": 5.0, "vertical": 900.0,
        "vertical_matmul": 700.0})
    rt = MapReduceRuntime(impl="auto")
    assert rt._auto_impl
    rng = np.random.default_rng(0)
    masks = rng.integers(0, 2**32, (200, 1), dtype=np.uint32)
    rt.scatter_db(masks, n_items=20)
    assert rt.impl == "matmul" and rt.impl in IMPLS


RETAIL_W = -(-16_470 // 32)      # FIMI retail: 515 words per mask


@pytest.mark.parametrize("family", ["pallas", "delta_pallas", "rules_pallas"])
def test_defaults_fit_main_path_widths_not_retail(family):
    assert at.defaults_fit(family, 6)
    assert not at.defaults_fit(family, RETAIL_W)


def test_static_tpu_fallbacks_take_jnp_past_vmem(monkeypatch, tmp_path):
    """With autotune off on a TPU, each static fallback takes its Pallas
    kernel only at widths whose default blocks fit VMEM: a catalog as wide
    as FIMI retail is counted by the jnp forms instead of being refused."""
    import types

    import jax
    from repro.core.mapreduce import MapReduceRuntime
    from repro.kernels.delta_count import delta_count
    from repro.kernels.ops import _support_count_jnp, support_count
    from repro.serving.rules_engine import RuleServeEngine
    _fresh(monkeypatch, tmp_path)
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(0)
    cands = np.zeros((8, RETAIL_W), np.uint32)
    cands[np.arange(8), rng.integers(0, RETAIL_W, 8)] = 1 << 3
    txns = rng.integers(0, 2**32, (64, RETAIL_W), dtype=np.uint32)

    assert np.array_equal(np.asarray(support_count(cands, txns)),
                          np.asarray(_support_count_jnp(cands, txns)))
    assert np.array_equal(
        delta_count(cands, txns[:40], txns[40:], autotune=False),
        delta_count(cands, txns[:40], txns[40:], impl="jnp", autotune=False))
    rt = MapReduceRuntime(impl="auto", autotune=False)
    assert rt.impl == "pallas"
    rt.scatter_db(txns, n_items=32 * RETAIL_W)
    assert rt.impl == "jnp"
    engine = types.SimpleNamespace(impl="auto", autotune=False,
                                   _backend="tpu")
    pick = lambda W: RuleServeEngine._resolve_impl(
        engine, types.SimpleNamespace(W=W), 32)
    assert pick(4) == "pallas" and pick(RETAIL_W) == "jnp"


@pytest.mark.slow
def test_plan_real_sweep_never_loses_to_single_family(monkeypatch, tmp_path):
    """Real timings: the joint winner is ≤ every single family it timed."""
    _fresh(monkeypatch, tmp_path)
    plan = at.tuned_plan("count", C=256, T=2048, W=4, kmax=8)
    assert plan is not None and plan["timed_us"]
    best = min(plan["timed_us"].values())
    assert plan["timed_us"][plan["family"]] == best
    assert "jnp" in plan["timed_us"]
