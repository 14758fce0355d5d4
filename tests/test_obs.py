"""Observability layer (DESIGN.md §13): deterministic span trees under
FakeClock (exact start/duration assertions, no sleeps), metrics-snapshot
schema golden tests (changing fields requires a schema-version bump),
disabled-tracer no-op guards, and Perfetto/Chrome-trace JSON validity
(required keys ``ph``/``ts``/``pid``/``tid``)."""

import json
import time

import numpy as np
import pytest

from loadgen import arrivals, constant_cost, drive, make_ruleset, tenant_mix
from repro.costmodel import CostController
from repro.costmodel.controller import Decision
from repro.costmodel.measure import time_once
from repro.costmodel.model import CostModel
from repro.obs import (NULL_TRACER, FakeClock, MonotonicClock, Registry,
                       Tracer, current_tracer, get_registry, set_registry,
                       use_tracer, validate_snapshot)
from repro.obs.metrics import (HISTOGRAM_FIELDS, SCHEMA_VERSION,
                               TOP_LEVEL_FIELDS)
from repro.obs.trace import NullTracer, set_tracer
from repro.obs.validate import main as validate_main
from repro.serving import OpenLoopServer, RuleServeEngine


@pytest.fixture()
def fresh_registry():
    """Swap in an empty process-wide registry; restore the old one after."""
    prev = get_registry()
    reg = set_registry(Registry())
    yield reg
    set_registry(prev)


# -- spans under FakeClock: exact, no sleeps -----------------------------------


def test_span_tree_exact_times():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("root", algo="vfpc") as root:
        clk.advance(1.0)
        with tr.span("child_a") as a:
            clk.advance(0.25)
        with tr.span("child_b", k=2) as b:
            clk.advance(0.5)
            b.event("midpoint")
        clk.advance(0.25)
    assert (root.t0, root.duration) == (0.0, 2.0)
    assert (a.t0, a.duration) == (1.0, 0.25)
    assert (b.t0, b.duration) == (1.25, 0.5)
    assert root.attrs["algo"] == "vfpc" and b.attrs["k"] == 2
    (ev,) = tr.events
    assert ev["name"] == "midpoint" and ev["ts"] == 1.75


def test_span_set_and_manual_close():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    s = tr.span("manual")
    clk.advance(3.0)
    s.set(result=7).close()
    s.close()                       # idempotent: t1 stays at first close
    assert s.duration == 3.0 and s.attrs["result"] == 7
    assert tr.current() is None


def test_nested_current_span_stack():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer") as outer:
        assert tr.current() is outer
        with tr.span("inner") as inner:
            assert tr.current() is inner
        assert tr.current() is outer
    assert tr.current() is None


def test_add_span_virtual_track():
    tr = Tracer(clock=FakeClock())
    s = tr.add_span("serve.query", 1.0, 3.5, tid="queries",
                    tenant="t0", outcome="served")
    assert s.duration == 2.5 and s.tid == "queries"


# -- Chrome-trace/Perfetto export ----------------------------------------------


def test_chrome_export_required_keys(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("root"):
        clk.advance(2.0)
        tr.event("decision.pass_width", args={"chosen": 2})
    tr.add_span("q", 0.5, 1.5, tid="queries")
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events, "export produced no events"
    for e in events:
        assert {"ph", "pid", "tid"} <= set(e), e
        if e["ph"] in ("X", "i"):
            assert "ts" in e and "name" in e, e
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert xs["root"]["dur"] == pytest.approx(2e6)     # µs
    assert xs["q"]["dur"] == pytest.approx(1e6)
    inst = [e for e in events if e["ph"] == "i"]
    assert inst and inst[0]["args"]["chosen"] == 2
    # thread-name metadata maps tid ints back to track names
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"main", "queries"} <= names


def test_chrome_export_normalizes_per_track():
    """Wall-clock and virtual-time tracks each start at ts=0."""
    clk = FakeClock(t0=1000.0)
    tr = Tracer(clock=clk)
    with tr.span("wall"):
        clk.advance(1.0)
    tr.add_span("virt", 2.0, 3.0, tid="queries")
    xs = {e["name"]: e for e in tr.to_chrome()["traceEvents"]
          if e["ph"] == "X"}
    assert xs["wall"]["ts"] == 0.0
    assert xs["virt"]["ts"] == 0.0


def test_chrome_export_closes_open_spans():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.span("leaked")
    clk.advance(4.0)
    (x,) = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert x["dur"] == pytest.approx(4e6)


def test_export_coerces_numpy_attrs(tmp_path):
    tr = Tracer(clock=FakeClock())
    tr.add_span("s", 0.0, 1.0, n=np.int64(3), frac=np.float32(0.5),
                arr=np.arange(2))
    path = tmp_path / "t.json"
    tr.export(str(path))
    args = [e for e in json.loads(path.read_text())["traceEvents"]
            if e["ph"] == "X"][0]["args"]
    assert args["n"] == 3.0 and args["frac"] == 0.5
    assert isinstance(args["arr"], str)    # non-scalar falls back to repr


# -- disabled-tracer fast path -------------------------------------------------


def test_null_tracer_is_default_and_singleton():
    assert current_tracer() is NULL_TRACER
    assert not NULL_TRACER.enabled
    s1 = NULL_TRACER.span("a", k=1)
    s2 = NULL_TRACER.span("b")
    assert s1 is s2                  # one shared null span, no allocation
    assert s1.set(x=1) is s1
    with s1 as s:
        s.event("ignored")
    assert NULL_TRACER.add_span("v", 0.0, 1.0) is s1
    assert NULL_TRACER.event("e") is None
    assert NULL_TRACER.current() is None


def test_null_tracer_overhead_guard():
    """Disabled tracing must stay O(dict build + dispatch) per call site —
    a very loose wall-time ceiling guards against accidental recording."""
    t0 = time.perf_counter()
    for _ in range(20_000):
        with current_tracer().span("hot", k=3, n=100):
            pass
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, f"null span path too slow: {elapsed:.3f}s / 20k"
    assert NULL_TRACER.spans == [] and NULL_TRACER.events == []


def test_use_tracer_scoping():
    tr = Tracer(clock=FakeClock())
    with use_tracer(tr):
        assert current_tracer() is tr
        with use_tracer(None):
            assert current_tracer() is NULL_TRACER
        assert current_tracer() is tr
    assert current_tracer() is NULL_TRACER
    set_tracer(tr)
    assert current_tracer() is tr
    set_tracer(None)
    assert current_tracer() is NULL_TRACER


# -- metrics registry + versioned snapshot schema ------------------------------


def test_registry_counters_gauges_histograms():
    reg = Registry()
    reg.counter("serving.offered", tenant="t0").inc()
    reg.counter("serving.offered", tenant="t0").inc(2)
    reg.counter("serving.offered", tenant="t1").inc()
    reg.gauge("serving.qps").set(1234.5)
    h = reg.histogram("serving.latency_ms", tenant="t0")
    for v in (0.2, 0.4, 3.0):
        h.observe(v)
    assert reg.value("serving.offered", tenant="t0") == 3
    assert reg.value("serving.offered", tenant="t1") == 1
    assert reg.value("no.such.metric") == 0.0
    snap = reg.snapshot()
    assert snap["counters"]["serving.offered{tenant=t0}"] == 3
    assert snap["gauges"]["serving.qps"] == 1234.5
    hs = snap["histograms"]["serving.latency_ms{tenant=t0}"]
    assert hs["count"] == 3 and hs["sum"] == pytest.approx(3.6)
    assert validate_snapshot(snap) == []


def test_histogram_percentiles_bucket_accurate():
    reg = Registry()
    h = reg.histogram("lat")
    for _ in range(98):
        h.observe(0.8)               # → 1.0 ms bucket
    h.observe(40.0)                  # → 50 ms bucket
    h.observe(200.0)                 # → 250 ms bucket
    assert h.percentile(50) == 1.0
    assert h.percentile(99) in (50.0, 250.0)
    assert h.percentile(100) == 250.0


def test_snapshot_schema_golden():
    """Schema v1 golden: these exact field sets ARE the versioned contract.
    If this test fails, bump ``repro.obs.metrics.SCHEMA_VERSION`` (and
    teach ``validate_snapshot`` the new version) instead of editing the
    assertion."""
    assert SCHEMA_VERSION == 1
    assert TOP_LEVEL_FIELDS == ("schema_version", "counters", "gauges",
                                "histograms")
    assert HISTOGRAM_FIELDS == ("buckets", "counts", "count", "sum",
                                "p50", "p99")
    reg = Registry()
    reg.counter("c").inc()
    reg.gauge("g").set(1)
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()
    assert set(snap) == set(TOP_LEVEL_FIELDS)
    assert set(snap["histograms"]["h"]) == set(HISTOGRAM_FIELDS)
    assert len(snap["histograms"]["h"]["counts"]) == \
        len(snap["histograms"]["h"]["buckets"]) + 1


def test_validate_snapshot_rejects_drift():
    good = Registry().snapshot()
    assert validate_snapshot(good) == []
    assert validate_snapshot([]) != []
    assert validate_snapshot({}) != []
    bad_version = dict(good, schema_version=99)
    assert any("schema_version" in e for e in validate_snapshot(bad_version))
    extra = dict(good, surprise=1)
    assert any("bump SCHEMA_VERSION" in e for e in validate_snapshot(extra))
    bad_counter = dict(good, counters={"c": "NaN-ish"})
    assert validate_snapshot(bad_counter) != []
    bad_hist = dict(good, histograms={"h": {"buckets": [], "counts": []}})
    assert validate_snapshot(bad_hist) != []


def test_validate_cli(tmp_path, capsys):
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(Registry().snapshot()))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema_version": 42}))
    assert validate_main([str(ok)]) == 0
    assert validate_main([str(bad)]) == 1
    assert validate_main([str(ok), str(bad)]) == 1
    out = capsys.readouterr().out
    assert "ok (schema v1" in out and "INVALID" in out


# -- clock unification (satellite: one injectable clock everywhere) ------------


def test_monotonic_clock_contract():
    clk = MonotonicClock()
    a, b = clk.now(), clk.now()
    assert b >= a


def test_time_once_accepts_fake_clock():
    clk = FakeClock()
    cost = time_once(lambda: clk.advance(0.01) and None, reps=3, clock=clk)
    assert cost == pytest.approx(0.01)


def test_loadgen_reexports_obs_fakeclock():
    import loadgen
    from repro.obs.clock import FakeClock as ObsFakeClock
    assert loadgen.FakeClock is ObsFakeClock


# -- cost-controller decision events with residual backfill --------------------


def test_decision_event_carries_residual():
    tr = Tracer(clock=FakeClock())
    ctrl = CostController(model=CostModel(persist=False))
    with use_tracer(tr):
        dec = ctrl._record(Decision("pass_width", "k", {"2": 1.0}, 2))
    (ev,) = tr.events
    assert ev["name"] == "decision.pass_width"
    assert ev["args"]["predicted_chosen"] == 1.0
    assert "measured" in ev["args"] and ev["args"]["measured"] is None
    dec.measured = 1.5          # observe_* backfill path
    assert ev["args"]["measured"] == 1.5
    assert ev["args"]["residual"] == pytest.approx(0.5)


def test_decisions_counted_in_registry(fresh_registry):
    ctrl = CostController(model=CostModel(persist=False))
    ctrl.should_admit(work=1.0, latency_slo_s=1.0)
    ctrl.should_admit(work=1.0, latency_slo_s=1.0)
    assert fresh_registry.value("costmodel.decisions", site="admission") == 2


def test_decision_without_tracer_has_no_trace_args():
    ctrl = CostController(model=CostModel(persist=False))
    dec = ctrl._record(Decision("pass_width", "k", {"2": 1.0}, 2))
    assert dec.trace_args is None
    dec.measured = 2.0          # must not blow up with tracing off
    assert dec.as_dict()["measured"] == 2.0
    assert "trace_args" not in dec.as_dict()


# -- traced mining: spans account for the run's wall-clock ---------------------


def _tiny_txns(seed=0, n=60, n_items=10):
    rng = np.random.default_rng(seed)
    return [sorted(set(rng.integers(0, n_items,
                                    rng.integers(2, 6)).tolist()))
            for _ in range(n)]


def test_traced_mine_span_taxonomy_and_wallclock(fresh_registry):
    from repro.core import mine
    tr = Tracer()
    with use_tracer(tr):
        res = mine(_tiny_txns(), n_items=10, min_sup=0.2)
    names = {s.name for s in tr.spans}
    assert {"mine.run", "mine.scatter", "mine.phase",
            "mine.gen", "mine.count"} <= names
    (run,) = [s for s in tr.spans if s.name == "mine.run"]
    phases = [s for s in tr.spans if s.name == "mine.phase"]
    assert len(phases) == res.n_phases
    # the run span and the reported wall-clock are the same boundaries
    assert run.duration == pytest.approx(res.total_seconds, rel=0.05)
    # per-level phase spans sum (within tolerance) to the run's wall-clock:
    # the gap is scatter + controller bookkeeping between phases
    phase_sum = sum(p.duration for p in phases)
    assert phase_sum <= run.duration * 1.001
    assert phase_sum >= 0.5 * run.duration
    # count spans carry the roofline achieved-vs-peak attributes (§10) on a
    # chip with published peaks; a CPU run has no device roofline and the
    # spans carry none rather than a share of made-up peaks
    counts = [s for s in tr.spans if s.name == "mine.count"]
    assert counts
    for c in counts:
        assert "count_seconds" in c.attrs
        assert not any(k.startswith("roofline_") for k in c.attrs)
    # registry mirrored the RuntimeStats increments 1:1
    assert fresh_registry.value("mine.dispatches") == res.dispatches
    assert fresh_registry.value("mine.compiles") == res.compiles
    snap = fresh_registry.snapshot()
    assert snap["gauges"]["mine.total_seconds"] == res.total_seconds
    assert validate_snapshot(snap) == []


def test_count_roofline_attrs_on_a_chip(monkeypatch):
    """On a device with published peaks a count job's span gets a bound and
    a share of the whole mesh's peak; an unknown device raises."""
    from types import SimpleNamespace

    import jax

    from repro.core.phases import count_roofline_attrs
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    runtime = SimpleNamespace(impl="vertical_pallas", _n_items=192,
                              mesh=SimpleNamespace(size=4))
    job = dict(n_candidates=4096, n_txns=200_000, n_words=6, kmax=3,
               seconds=0.01)
    attrs = count_roofline_attrs(runtime, **job)
    assert attrs["roofline_bound"] == "memory"
    assert attrs["roofline_peak"] == 4 * 819e9
    assert 0.0 < attrs["roofline_peak_frac"] <= 1.0
    monkeypatch.setattr(chip, "device_kind", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="no published peaks"):
        count_roofline_attrs(runtime, **job)


def test_untraced_mine_records_nothing(fresh_registry):
    from repro.core import mine
    assert current_tracer() is NULL_TRACER
    res = mine(_tiny_txns(1), n_items=10, min_sup=0.2)
    assert res.n_phases >= 1
    assert NULL_TRACER.spans == [] and NULL_TRACER.events == []


def test_traced_stream_miner_spans():
    from repro.stream import StreamMiner
    tr = Tracer()
    with use_tracer(tr):
        miner = StreamMiner(10, 0.3, capacity=64, refresh_rules=True)
        miner.push(_tiny_txns(2, n=48))
        miner.push(_tiny_txns(3, n=16))
    names = [s.name for s in tr.spans]
    assert "stream.update" in names and "stream.remine" in names
    updates = [s for s in tr.spans if s.name == "stream.update"]
    assert [u.attrs["path"] for u in updates] == \
        [u.path for u in miner.updates]
    for u in updates:
        assert u.t1 is not None and u.attrs["window"] == u.attrs["window"]


# -- traced serving: per-query admission→dispatch spans + tenant histograms ----


@pytest.fixture(scope="module")
def ruleset():
    return make_ruleset(7)


def test_open_loop_server_feeds_registry_and_trace(ruleset):
    rules, baskets = ruleset
    from repro.serving import RuleStore
    store = RuleStore(tenants={"t0": rules, "t1": rules})
    eng = RuleServeEngine(store, impl="jnp", top_k=3, autotune=False)
    ctrl = CostController(model=CostModel(persist=False))
    reg = Registry()
    tr = Tracer(clock=FakeClock())
    n = 60
    times = arrivals(50.0, n, seed=3)          # light load: nothing sheds
    tenants = tenant_mix(["t0", "t1"], n, seed=4, weights=[4, 1])
    with use_tracer(tr):
        srv = OpenLoopServer(eng, latency_slo_ms=20.0, batch=8,
                             max_wait_ms=5.0, cache_size=32, controller=ctrl,
                             dispatch_cost_fn=constant_cost(0.001),
                             registry=reg, clock=FakeClock())
        drive(srv, [baskets[i % 10] for i in range(n)],   # repeats → cache hits
              times, tenants)
    s = srv.summary()
    assert s["n_queries"] == n
    # per-tenant offered/admitted/shed counters reconcile with the summary
    offered = sum(reg.value("serving.offered", tenant=t)
                  for t in ("t0", "t1"))
    assert offered == n
    shed = sum(reg.value("serving.shed", tenant=t) for t in ("t0", "t1"))
    assert shed == s["shed"]
    # per-tenant latency histograms cover every answered query
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    answered = sum(h["count"] for k, h in snap["histograms"].items()
                   if k.startswith("serving.latency_ms"))
    assert answered == s["served"] + s["cached"]
    # virtual-time trace: one serve.query span per submitted query,
    # dispatch spans on their own device track
    qspans = [sp for sp in tr.spans if sp.name == "serve.query"]
    assert len(qspans) == n
    outcomes = {sp.attrs["seq"]: sp.attrs["outcome"] for sp in qspans}
    for o in srv.outcomes:
        assert outcomes[o.seq] == o.outcome
    served_spans = [sp for sp in qspans if sp.attrs["outcome"] == "served"]
    for sp in served_spans:
        assert sp.duration > 0 and sp.attrs["queue_wait_ms"] >= 0
    dspans = [sp for sp in tr.spans if sp.name == "serve.dispatch"]
    assert len(dspans) == s["dispatches"]
    assert all(sp.tid == "device" for sp in dspans)
    # headline gauges landed in the registry
    assert reg.value("serving.qps") > 0
    assert reg.value("serving.shed_rate") == pytest.approx(s["shed_rate"])


def test_cache_counters_back_compat(ruleset):
    from repro.serving.admission import ResultCache
    cache = ResultCache(capacity=4)
    assert cache.get("t", 0, [1, 2], 3) is None
    cache.put("t", 0, [1, 2], 3, ["r"])
    assert cache.get("t", 0, [1, 2], 3) == ["r"]
    assert (cache.hits, cache.misses) == (1, 1)
    assert isinstance(cache.hits, int)


# -- report.py --trace rendering -----------------------------------------------


def test_report_trace_tables(tmp_path, capsys):
    from repro.launch.report import (load_trace, report_trace, trace_spans)
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("mine.run"):
        clk.advance(0.1)
        with tr.span("mine.phase"):
            clk.advance(0.8)
        clk.advance(0.1)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    spans = trace_spans(load_trace(str(path)))
    by_name = {s["name"]: s for s in spans}
    # self time subtracts nested spans on the same track
    assert by_name["mine.run"]["dur"] == pytest.approx(1e6)
    assert by_name["mine.run"]["self_us"] == pytest.approx(0.2e6)
    assert by_name["mine.phase"]["self_us"] == pytest.approx(0.8e6)
    report_trace(str(path), top=5)
    out = capsys.readouterr().out
    assert "slowest spans" in out and "mine.phase" in out
    assert "Per-phase time breakdown" in out


def test_report_decisions_accepts_stream_payload(tmp_path, capsys):
    from repro.launch.report import load_decisions, report_decisions
    rows = [{"site": "remine", "key": "k", "chosen": True,
             "predicted": {"remine": 0.5}, "measured": 0.6}]
    stream_shaped = tmp_path / "stream.json"
    stream_shaped.write_text(json.dumps(
        {"updates_per_s": 10.0, "paths": {"delta": 3}, "decisions": rows}))
    assert load_decisions(str(stream_shaped)) == rows
    report_decisions(str(stream_shaped))
    assert "remine" in capsys.readouterr().out
    # a payload without decisions degrades to a hint, not a crash
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps({"updates_per_s": 10.0}))
    assert load_decisions(str(legacy)) == []
    report_decisions(str(legacy))
    assert "no decision rows" in capsys.readouterr().out
