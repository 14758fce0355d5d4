"""Bring-up smoke of the main path on a TPU: mine → rules → serve → stream.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Phases on one chip, through the entry points behind ``launch/mine.py``,
``launch/serve_rules.py`` and ``launch/stream.py``:

  device   the first JAX device must be a TPU; nothing falls back to the CPU.
  kernels  every Pallas kernel, compiled for the chip, against its jnp twin on
           seeded data at main-path widths (bit-exact).
  mine     c20d200k at scale 1.0 (200,000 transactions, 192 items, mean width
           20), min_sup 0.25, optimized_vfpc on a 1x1 mesh with elastic=False:
           impl="auto" once, then every counting form in ``IMPLS`` that is not
           ``*_interpret``.  Itemsets must equal ``sequential_apriori``.
           Each mine is traced: every count job's span must hold its
           host steps (``mine.count.prep``, ``mine.count.wait``) and carry
           ``count_seconds``.
  serve    mushroom at scale 1.0, min_sup 0.35, min_conf 0.7; 256 seeded
           queries in batches of 16 through ``RuleServeEngine`` with jnp,
           pallas and matmul_pallas.  Recommendations must be identical.
  stream   ``StreamMiner`` over mushroom, capacity 1024, 8 updates of 16, with
           pallas, matmul_pallas and jnp delta counting.  The published levels
           must equal a from-scratch mine of the window.

With ``--chips 4`` it mines c20d200k on a 4x1 and a 2x2 ``make_mining_mesh``
(candidates sharded over ``cand``) and on one chip, and compares the three
sets of itemsets, each mine traced and its count spans checked as above; it
runs no other phase.

Every step prints one line: phase, impl, wall seconds, XLA compiles, compile
seconds, persistent-cache hits and whether the answer matched.  The last line
of standard output is one JSON object naming the device, printed only when
every phase passed; otherwise the exit code is 1.  Set
``JAX_COMPILATION_CACHE_DIR`` to keep compiled programs between runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.core import generate_ruleset, mine, sequential_apriori  # noqa: E402
from repro.core.bitset import (jpack_bits, pack_itemsets,  # noqa: E402
                               vertical_pack)
from repro.core.mapreduce import IMPLS, MapReduceRuntime  # noqa: E402
from repro.data import dataset_by_name  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mining_mesh  # noqa: E402
from repro.launch.serve_rules import make_queries  # noqa: E402
from repro.obs.trace import Tracer, use_tracer  # noqa: E402

C20_SCALE = 1.0           # c20d200k at full size: 200,000 transactions
MINE_SUP = 0.25
KERNEL_SHAPES = {"C": 4096, "T": 8192, "vertical_rows": 200_000,
                 "R": 2048, "Q": 256}
RULES_SUP, RULES_CONF = 0.35, 0.7
N_QUERIES, QUERY_BATCH = 256, 16
CAPACITY, N_UPDATES, UPDATE_ROWS = 1024, 8, 16
SEED = 0

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """XLA compiles (with their seconds) and persistent-cache hits, from
    JAX's monitoring events."""

    def __init__(self):
        self.n, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1

    def snapshot(self):
        return self.n, self.seconds, self.hits


class Smoke:
    """Runs steps, prints one line each, remembers which failed."""

    def __init__(self):
        self.compiles = CompileCounter()
        self.failed: list[str] = []

    def step(self, phase: str, impl: str, fn, check: bool = True):
        """Run ``fn`` and return its result.  With ``check`` it returns
        ``(matched, note)``; a set-up step (``check=False``) returns data.
        An exception fails the step (its traceback goes to stderr) and the
        remaining steps still run."""
        c0 = self.compiles.snapshot()
        t0 = time.perf_counter()
        out = None
        try:
            out = fn()
            matched, note = out if check else ("n/a", "")
        except Exception:
            traceback.print_exc()
            matched, note = False, "raised"
        wall = time.perf_counter() - t0
        n, secs, hits = (a - b for a, b in zip(self.compiles.snapshot(), c0))
        print(f"phase={phase} impl={impl} wall_s={wall:.6f} compiles={n} "
              f"compile_s={secs:.6f} cache_hits={hits} match={matched}"
              + (f" {note}" if note else ""), flush=True)
        if matched is False:
            self.failed.append(f"{phase}[{impl}]")
        return out


def _subsets(rng, n: int, n_items: int, lo: int, hi: int) -> np.ndarray:
    """n random itemsets of lo..hi items as (n, n_items) bool rows."""
    out = np.zeros((n, n_items), bool)
    for i, k in enumerate(rng.integers(lo, hi + 1, n)):
        out[i, rng.choice(n_items, k, replace=False)] = True
    return out


def kernel_checks(smoke: Smoke) -> None:
    """Each Pallas kernel against its jnp twin at main-path widths
    (``KERNEL_SHAPES``): c20d200k W=6 with 4,096 candidates × 8,192 rows,
    the vertical layout of 200,000 rows (6,250 words) with kmax=3, and
    mushroom-width rules R=2,048 against Q=256 baskets."""
    from repro.kernels.delta_count import (delta_count_jnp,
                                           delta_count_matmul_pallas,
                                           delta_count_pallas)
    from repro.kernels.ops import _support_count_jnp
    from repro.kernels.rule_match import (rule_scores_jnp,
                                          rule_scores_matmul_pallas,
                                          rule_scores_pallas)
    from repro.kernels.support_count import (support_count_matmul_pallas,
                                             support_count_pallas)
    from repro.kernels.vertical_count import (vertical_count_jnp,
                                              vertical_count_matmul_pallas,
                                              vertical_count_pallas)
    shp = KERNEL_SHAPES
    rng = np.random.default_rng(SEED)
    C, T, n_items = shp["C"], shp["T"], 192
    cands = jpack_bits(jnp.asarray(_subsets(rng, C, n_items, 1, 3)))
    txns = jpack_bits(jnp.asarray(rng.random((T, n_items)) < 0.5))
    signs = jnp.asarray(rng.choice(np.array([-1, 0, 1], np.int32), T))

    def same(got, want, hit=lambda w: w != 0):
        got, want = np.asarray(got), np.asarray(want)
        hits = int(np.count_nonzero(hit(want)))
        return bool(np.array_equal(got, want)), f"hits={hits}/{want.size}"

    ref = np.asarray(_support_count_jnp(cands, txns))
    for name, fn in (("support_count_pallas", support_count_pallas),
                     ("support_count_matmul_pallas",
                      support_count_matmul_pallas)):
        smoke.step("kernels", name, lambda fn=fn: same(fn(cands, txns), ref))
    ref = np.asarray(delta_count_jnp(cands, txns, signs))
    for name, fn in (("delta_count_pallas", delta_count_pallas),
                     ("delta_count_matmul_pallas", delta_count_matmul_pallas)):
        smoke.step("kernels", name,
                   lambda fn=fn: same(fn(cands, txns, signs), ref))

    db = np.asarray(jpack_bits(jnp.asarray(
        rng.random((shp["vertical_rows"], n_items)) < 0.5)))
    vdb = jnp.asarray(vertical_pack(db, n_items))          # (193, rows/32)
    idx = np.full((C, 3), n_items, np.int32)
    for i, k in enumerate(rng.integers(1, 4, C)):
        idx[i, :k] = rng.choice(n_items, k, replace=False)
    idx = jnp.asarray(idx)
    ref = np.asarray(vertical_count_jnp(vdb, idx))
    for name, fn in (("vertical_count_pallas", vertical_count_pallas),
                     ("vertical_count_matmul_pallas",
                      vertical_count_matmul_pallas)):
        smoke.step("kernels", name, lambda fn=fn: same(fn(vdb, idx), ref))

    R, Q, r_items = shp["R"], shp["Q"], 119
    antes = _subsets(rng, R, r_items, 1, 2)
    cons = _subsets(rng, R, r_items, 1, 1) & ~antes
    scores = jnp.asarray(rng.random(R, dtype=np.float32))
    antes, cons = (jpack_bits(jnp.asarray(a)) for a in (antes, cons))
    baskets = jpack_bits(jnp.asarray(rng.random((Q, r_items)) < 0.3))
    ref = np.asarray(rule_scores_jnp(antes, cons, scores, baskets))
    for name, fn in (("rule_scores_pallas", rule_scores_pallas),
                     ("rule_scores_matmul_pallas", rule_scores_matmul_pallas)):
        smoke.step("kernels", name, lambda fn=fn: same(
            fn(antes, cons, scores, baskets), ref, hit=np.isfinite))


def load_c20d200k(smoke: Smoke):
    data = smoke.step("setup", "c20d200k_generate", lambda: (
        dataset_by_name("c20d200k", seed=SEED, scale=C20_SCALE)),
        check=False)
    txns, n_items = data
    db = pack_itemsets([list(t) for t in txns], n_items)
    return txns, n_items, db


def _mine_on(mesh, db, n_items, impl=None, cand_axis=None, autotune=True):
    """One traced mine; returns the runtime, the result and the span
    check of its count jobs (:func:`_count_spans_ok`)."""
    rt = MapReduceRuntime(mesh=mesh, impl=impl, cand_axis=cand_axis,
                          autotune=autotune)
    tracer = Tracer()
    with use_tracer(tracer):
        res = mine(db_masks=db, n_items=n_items, min_sup=MINE_SUP,
                   algorithm="optimized_vfpc", runtime=rt, elastic=False)
    return rt, res, _count_spans_ok(tracer)


def _count_spans_ok(tracer: Tracer) -> tuple[bool, str]:
    """Every ``mine.count`` span holds one ``mine.count.prep`` and one
    ``mine.count.wait`` span and carries ``count_seconds``."""
    spans = [s for s in tracer.spans if s.name == "mine.count"]

    def children(c, name):
        return [s for s in tracer.spans if s.name == name
                and c.t0 <= s.t0 and s.t1 <= c.t1]

    ok = bool(spans) and all(
        len(children(c, "mine.count.prep")) == 1
        and len(children(c, "mine.count.wait")) == 1
        and "count_seconds" in c.attrs for c in spans)
    wait = sum(s.duration for s in tracer.spans
               if s.name == "mine.count.wait")
    return ok, f"count_jobs={len(spans)} count_wait_s={wait:.6f}"


def _levels_note(itemsets: dict) -> str:
    return "levels=" + "/".join(str(len(itemsets[k])) for k in sorted(itemsets))


def mine_phase(smoke: Smoke) -> None:
    txns, n_items, db = load_c20d200k(smoke)
    oracle = smoke.step("setup", "sequential_apriori",
                        lambda: sequential_apriori(txns, MINE_SUP),
                        check=False)

    def run(impl):
        rt, res, (spans_ok, spans_note) = _mine_on(
            make_mining_mesh(1, 1), db, n_items, impl=impl,
            autotune=impl is None)
        got = res.itemsets()
        return got == oracle and spans_ok, \
            (f"chose={rt.impl} " if impl is None else "") \
            + _levels_note(got) + f" phases={res.n_phases} {spans_note}"

    smoke.step("mine", "auto", lambda: run(None))
    for impl in IMPLS:
        if not impl.endswith("_interpret"):
            smoke.step("mine", impl, lambda impl=impl: run(impl))


def serve_phase(smoke: Smoke) -> None:
    from repro.serving import RuleServeEngine
    txns, n_items = dataset_by_name("mushroom", seed=SEED, scale=1.0)
    rules = smoke.step("setup", "mushroom_rules", lambda: generate_ruleset(
        mine(txns, n_items=n_items, min_sup=RULES_SUP), RULES_CONF),
        check=False)
    queries = make_queries(txns, N_QUERIES, seed=SEED)
    batches = [queries[i:i + QUERY_BATCH]
               for i in range(0, N_QUERIES, QUERY_BATCH)]
    answers = {}

    def run(impl):
        results, records = RuleServeEngine(rules, impl=impl).serve(batches)
        answers[impl] = results
        n_recs = sum(len(r) for batch in results for r in batch)
        note = (f"rules={len(rules)} queries={N_QUERIES} "
                f"dispatches={len(records)} recs={n_recs}")
        return results == answers["jnp"], note

    for impl in ("jnp", "pallas", "matmul_pallas"):
        smoke.step("serve", impl, lambda impl=impl: run(impl))


def stream_phase(smoke: Smoke) -> None:
    from repro.stream import StreamMiner
    from repro.stream.tables import levels_equal
    txns, n_items = dataset_by_name("mushroom", seed=SEED, scale=1.0)

    def run(impl):
        sm = StreamMiner(n_items, RULES_SUP, capacity=CAPACITY,
                         min_confidence=RULES_CONF, impl=impl)
        sm.push(txns[:CAPACITY])
        paths = []
        for u in range(N_UPDATES):
            lo = CAPACITY + u * UPDATE_ROWS
            paths.append(sm.push(txns[lo:lo + UPDATE_ROWS]).path)
        scratch = mine(db_masks=sm.window.contents(), n_items=n_items,
                       min_sup=RULES_SUP)
        return (levels_equal(sm.levels, scratch.levels),
                f"window={sm.window.size} frequent={sm.n_frequent} "
                f"paths={','.join(paths)}")

    for impl in ("pallas", "matmul_pallas", "jnp"):
        smoke.step("stream", impl, lambda impl=impl: run(impl))


def mesh_phase(smoke: Smoke, n_chips: int) -> None:
    """c20d200k on a 4x1 and a 2x2 mining mesh and on one chip."""
    _, n_items, db = load_c20d200k(smoke)
    found = {}

    def run(name, mesh, cand_axis=None):
        rt, res, (spans_ok, spans_note) = _mine_on(mesh, db, n_items,
                                                   cand_axis=cand_axis)
        found[name] = res.itemsets()
        return (found[name] == found.get("1x1", found[name]) and spans_ok,
                f"mesh={rt.mesh_split[0]}x{rt.mesh_split[1]} impl={rt.impl} "
                + _levels_note(found[name]) + f" {spans_note}")

    smoke.step("mesh", "1x1", lambda: run(
        "1x1", make_mesh((1, 1), ("data", "cand"), jax.devices()[:1])))
    smoke.step("mesh", f"{n_chips}x1",
               lambda: run(f"{n_chips}x1", make_mining_mesh(n_chips, 1)))
    smoke.step("mesh", "2x2", lambda: run(
        "2x2", make_mining_mesh(2, 2), cand_axis="cand"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the (data, cand) mesh phase on four chips")
    args = ap.parse_args()
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: the first JAX device is {dev.platform!r}, "
                 f"not a TPU; nothing here runs on the CPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")
    print(f"phase=device impl=- kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)

    smoke = Smoke()
    if args.chips == 4:
        mesh_phase(smoke, 4)
    else:
        kernel_checks(smoke)
        mine_phase(smoke)
        serve_phase(smoke)
        stream_phase(smoke)
    if smoke.failed:
        sys.exit(f"chip_smoke: failed: {', '.join(smoke.failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
