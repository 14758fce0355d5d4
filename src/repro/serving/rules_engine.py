"""Batched association-rule serving — the mine → rules → serve endgame
(DESIGN.md §7, multi-tenant since §12).

Incoming basket queries are bit-packed into transaction bitsets (§2) and
matched against rule antecedents with the same word-parallel ``(c & t) == c``
containment test the counting kernels use — ``kernels/rule_match.py`` provides
the Pallas variant and the blocked-jnp oracle, block sizes autotuned via
``kernels/autotune.py`` (§5).  Each dispatch emits the masked (Q, R)
confidence·lift score matrix and reduces it with a device-side
``lax.top_k``; only the (Q, k) winners cross back to the host.

Micro-batching: queued query batches are fused per dispatch by the same
pass-combining ``Policy`` objects the mining drivers and the LM
:class:`~repro.serving.engine.ServeEngine` share (``core/policy.py``).  The
isomorphism: one dispatch answering ``npass`` queued batches is the serving
analogue of one counting job covering ``npass`` Apriori levels — candidate
count |C| maps to rule·query pairs scored, |L| to queries answered.  The SPC
policy reproduces strict per-batch dispatch (the "unfused" benchmark arm).

Multi-tenant serving (DESIGN.md §12): the engine sits on a
:class:`~repro.serving.rule_store.RuleStore` — a tenant registry of versioned
RuleSets packed into one device-resident arena — so one fused dispatch serves
*mixed-tenant* query batches; per-tenant tag bits in the packed baskets keep
isolation inside the unchanged containment test.  Constructing the engine
from a bare RuleSet wraps it in a single-tenant store (byte-identical to the
PR 5 layout), and queries may be bare baskets (default tenant) or
``(tenant, basket)`` pairs.

Live rule refresh (DESIGN.md §8/§12): everything derived from the registry —
device arrays, float64 metric columns, the per-shape jit cache — is bundled
into one immutable :class:`~repro.serving.rule_store.ArenaState`, and
:meth:`RuleServeEngine.swap_rules` replaces the whole bundle with a single
reference assignment.  A serve call captures the state once, so in-flight
queries never observe a half-swapped ("torn") rule table; the next call sees
the fresh rules.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import ALGORITHMS, PhaseStats
from repro.core.rules import RuleSet
from repro.obs.trace import current_tracer
from repro.kernels.autotune import (DEFAULTS, defaults_fit, tuned_blocks,
                                    tuned_plan)
from repro.kernels.ops import pallas_interpret
from repro.kernels.rule_match import (rule_scores_jnp, rule_scores_matmul,
                                      rule_scores_matmul_pallas,
                                      rule_scores_pallas)
from repro.roofline import XFER_OPS_PER_BYTE

from .common import MIN_QUERY_BUCKET, bucket_rows
from .rule_store import DEFAULT_TENANT, ArenaState, RuleStore

RULE_IMPLS = ("auto", "jnp", "pallas", "pallas_interpret", "matmul",
              "matmul_pallas", "matmul_pallas_interpret")


@dataclasses.dataclass(frozen=True)
class Recommendation:
    consequent: tuple       # item ids the rule recommends
    confidence: float       # exact float64, from the RuleSet's integer counts
    lift: float
    score: float            # float32 confidence·lift rank key (device value)


@dataclasses.dataclass
class RuleServeRecord:
    phase_idx: int
    n_batches: int          # queued query batches fused into this dispatch
    n_queries: int
    elapsed: float


def as_tenant_pairs(batch, tenant: str | None = None) -> list:
    """Normalize one query batch to ``(tenant, basket)`` pairs.

    ``tenant`` (when given) applies to every query; otherwise a 2-tuple whose
    first element is a str is already a pair and a bare basket gets
    :data:`DEFAULT_TENANT`.
    """
    if tenant is not None:
        return [(tenant, basket) for basket in batch]
    out = []
    for q in batch:
        if (isinstance(q, tuple) and len(q) == 2
                and isinstance(q[0], str)):
            out.append(q)
        else:
            out.append((DEFAULT_TENANT, q))
    return out


class RuleServeEngine:
    """Answer basket queries with top-k rule consequents by confidence·lift.

    Args:
      rules: a RuleSet from ``core.rules.generate_ruleset`` (wrapped in a
        single-tenant :class:`RuleStore`), or a RuleStore for multi-tenant
        serving through the packed arena (DESIGN.md §12).
      top_k: default number of recommendations per query.
      impl: one of ``RULE_IMPLS`` — the containment scoring path: popcount
        ("jnp"/"pallas") or bit-plane matmul ("matmul"/"matmul_pallas",
        DESIGN.md §10) forms; "auto" resolves per dispatch shape to the
        cross-family autotune plan winner when autotune is on (static
        fallback: pallas on TPU, matmul on GPU, jnp elsewhere); plain
        "*pallas" names raise off the TPU, like the counting kernels — the
        "*_interpret" names run the kernels in the Pallas interpreter.
      algorithm: pass-combining policy fusing queued query batches per
        dispatch (core/policy.py; "spc" = strict per-batch dispatch).
      max_fuse: cap on batches fused into one dispatch.
      exclude_contained: drop rules whose consequent the basket already
        contains (nothing new to recommend) — fused into the scoring kernel.
      dedup_consequents: return k *distinct* consequents per query (several
        rules can share one); the device top-k overfetches ``overfetch``×k
        rule slots and the host decode keeps each consequent's best-scoring
        hit.  False returns raw rule-level top-k.
      overfetch: rule slots fetched per requested consequent when deduping
        (clamped to the rule count; a bound, not a guarantee, when one
        consequent dominates more than that many rules).
      autotune: consult the block-size autotuner; False pins static defaults.
      latency_budget_ms: per-dispatch latency budget for the ``measured``
        algorithm — fuse the most batches whose predicted dispatch time
        stays under it (None: fuse maximally, pure throughput).
      controller: :class:`repro.costmodel.CostController` for the
        ``measured`` algorithm's fusion decisions (DESIGN.md §9); default
        shares the process-wide model.
    """

    def __init__(self, rules: RuleSet | RuleStore, *, top_k: int = 5,
                 impl: str = "auto", algorithm: str = "optimized_vfpc",
                 policy_kwargs: dict | None = None, max_fuse: int = 16,
                 exclude_contained: bool = True,
                 dedup_consequents: bool = True, overfetch: int = 8,
                 autotune: bool = True, latency_budget_ms: float | None = None,
                 controller=None):
        if impl not in RULE_IMPLS:
            raise ValueError(f"unknown impl {impl!r}; options: {RULE_IMPLS}")
        if algorithm not in ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {algorithm!r}; options: {sorted(ALGORITHMS)}")
        backend = jax.default_backend()
        self._backend = backend
        # "auto" stays unresolved here: _fn resolves it per dispatch shape
        # from the cross-family plan (DESIGN.md §10)
        self.impl = impl
        self.top_k = top_k
        self.max_fuse = max_fuse
        self.exclude_contained = exclude_contained
        self.dedup_consequents = dedup_consequents
        self.overfetch = max(int(overfetch), 1)
        self.autotune = autotune
        self.algorithm = algorithm
        self.latency_budget_s = (None if latency_budget_ms is None
                                 else float(latency_budget_ms) / 1e3)
        if algorithm == "measured":
            # cost-model fusion: no Policy object — choose_fusion is the
            # serving primitive (DESIGN.md §9)
            if controller is None:
                from repro.costmodel import CostController
                controller = CostController()
            self.policy = None
        else:
            policy_cls, _ = ALGORITHMS[algorithm]
            self.policy = policy_cls(**(policy_kwargs or {}))
        # a controller passed alongside a paper policy still observes every
        # dispatch, so baseline runs calibrate the model the measured mode uses
        self.controller = controller

        self.store = rules if isinstance(rules, RuleStore) else RuleStore(rules)
        self.records: list[RuleServeRecord] = []

    @property
    def rules(self) -> RuleSet:
        return self.store.state.rules          # sole tenant (raises if many)

    @property
    def n_rules(self) -> int:
        return len(self.store.state)

    @property
    def tenants(self) -> tuple:
        return self.store.tenants

    @property
    def dispatches(self) -> int:
        return len(self.records)

    # -- live refresh ----------------------------------------------------------

    def swap_rules(self, rules: RuleSet, warm_to: int | None = None,
                   tenant: str | None = None) -> None:
        """Atomically replace one tenant's served RuleSet (DESIGN.md §8/§12).

        The complete successor arena (device arrays, metric columns, empty
        jit cache) is built first — optionally pre-compiled up to ``warm_to``
        queries so the first post-swap dispatch pays no compile cost — and
        then published with one reference assignment.  Serve calls capture
        the state once, so a query stream never sees a torn table: each
        dispatch is answered entirely by the old arena or entirely by the
        new one.  ``tenant`` defaults to the sole registered tenant.
        """
        if tenant is None:
            names = self.store.tenants
            tenant = names[0] if len(names) == 1 else DEFAULT_TENANT
        warm = ((lambda state: self._warm(state, warm_to, self.top_k))
                if warm_to else None)
        self.store.swap_rules(tenant, rules, warm=warm)

    # -- jitted dispatch -------------------------------------------------------

    def _blocks(self, state: ArenaState, impl_key: str, Qp: int) -> dict:
        if not self.autotune:
            return dict(DEFAULTS[impl_key])
        return tuned_blocks(impl_key, C=max(len(state), 1), T=Qp, W=state.W)

    def _resolve_impl(self, state: ArenaState, Qp: int) -> str:
        impl = self.impl
        if impl != "auto":
            return impl
        plan = (tuned_plan("rules", C=max(len(state), 1), T=Qp, W=state.W)
                if self.autotune else None)
        if plan is not None and plan["impl"] in RULE_IMPLS:
            return plan["impl"]
        if self._backend == "tpu" and defaults_fit("rules_pallas", state.W):
            return "pallas"
        return "matmul" if self._backend == "gpu" else "jnp"

    def _fn(self, state: ArenaState, Qp: int, k: int):
        key = (Qp, k)
        if key in state.jitted:
            return state.jitted[key]
        ante, cons, scores = state.d_ante, state.d_cons, state.d_scores
        excl = self.exclude_contained
        impl = self._resolve_impl(state, Qp)
        if impl in ("jnp", "matmul"):
            blocks = self._blocks(state, f"rules_{impl}", Qp)
            qb = min(blocks["q_block"], Qp)
            score_fn = rule_scores_matmul if impl == "matmul" else rule_scores_jnp

            def fn(baskets):
                s = score_fn(ante, cons, scores, baskets,
                             q_block=qb, exclude_contained=excl)
                return jax.lax.top_k(s, k)
        else:
            interpret = pallas_interpret(impl)
            base = ("rules_matmul_pallas" if impl.startswith("matmul")
                    else "rules_pallas")
            impl_key = f"{base}_interpret" if interpret else base
            blocks = self._blocks(state, impl_key, Qp)
            score_fn = (rule_scores_matmul_pallas if impl.startswith("matmul")
                        else rule_scores_pallas)

            def fn(baskets):
                s = score_fn(ante, cons, scores, baskets,
                             bq=blocks["bq"], br=blocks["br"],
                             exclude_contained=excl,
                             interpret=interpret)
                return jax.lax.top_k(s, k)
        state.jitted[key] = jax.jit(fn)
        return state.jitted[key]

    def _dispatch(self, state: ArenaState, packed: np.ndarray, k: int):
        """(Q, W) packed baskets → host (Q, k) score values + rule indices."""
        Q = packed.shape[0]
        Qp = bucket_rows(Q)
        if Qp != Q:
            packed = np.concatenate(
                [packed, np.zeros((Qp - Q, state.W), np.uint32)], axis=0)
        vals, idx = self._fn(state, Qp, k)(jnp.asarray(packed))
        return np.asarray(vals)[:Q], np.asarray(idx)[:Q]

    def _warm(self, state: ArenaState, max_queries: int,
              top_k: int | None = None):
        k = max(min(self.top_k if top_k is None else top_k, len(state)), 0)
        if k == 0:
            return
        kf = min(k * self.overfetch, len(state)) if self.dedup_consequents else k
        b = MIN_QUERY_BUCKET
        while True:
            self._dispatch(state, np.zeros((b, state.W), np.uint32), kf)
            if b >= max_queries:
                break
            b *= 2

    def warmup(self, max_queries: int, top_k: int | None = None):
        """Pre-compile every pow2 query bucket up to ``max_queries`` (and run
        the autotuner) so no dispatch in the serving loop pays compile cost."""
        self._warm(self.store.state, max_queries, top_k)

    # -- host driver -----------------------------------------------------------

    def _decode(self, state: ArenaState, vals: np.ndarray, idx: np.ndarray,
                k: int):
        dedup = self.dedup_consequents
        out = []
        for q in range(vals.shape[0]):
            recs = []
            seen: set = set()
            for j in range(vals.shape[1]):
                # -inf is the kernel's no-match sentinel; +inf is a legal score
                # (legacy missing-consequent lift) and must decode normally
                if np.isneginf(vals[q, j]) or len(recs) >= k:
                    break
                r = int(idx[q, j])
                cons = state.cons_tuple(r)
                if dedup:
                    if cons in seen:
                        continue    # a lower-scored rule for the same consequent
                    seen.add(cons)
                recs.append(Recommendation(
                    cons, float(state.conf64[r]), float(state.lift64[r]),
                    float(vals[q, j])))
            out.append(recs)
        return out

    def serve(self, batches, top_k: int | None = None,
              tenant: str | None = None):
        """Answer a queue of basket batches with policy-fused dispatches.

        Args:
          batches: sequence of batches; each batch is a list of queries — a
            query is a basket (iterable of item ids, served under the
            default tenant) or a ``(tenant, basket)`` pair; mixed-tenant
            batches share one fused arena dispatch (DESIGN.md §12).
          top_k: recommendations per query (default: engine top_k).
          tenant: serve every query under this tenant (overrides pairs).

        Returns ``(results, records)`` — ``results[b][q]`` is the list of
        :class:`Recommendation` for basket ``q`` of batch ``b``, and
        ``records`` the per-dispatch :class:`RuleServeRecord` trace (also kept
        on ``self.records``).
        """
        state = self.store.state     # snapshot: one consistent table per call
        n_rules = len(state)
        k = max(min(self.top_k if top_k is None else top_k, n_rules), 0)
        batches = [as_tenant_pairs(b, tenant) for b in batches]
        results: list = []
        records: list[RuleServeRecord] = []
        history: list[PhaseStats] = []
        if n_rules == 0 or k == 0:            # no rules: everything is empty
            results = [[[] for _ in b] for b in batches]
            self.records = records
            return results, records

        i, phase_idx = 0, 0
        while i < len(batches):
            if self.policy is None:   # measured: predicted latency vs budget
                # per-query work: rule·word containment tests plus the top-k
                # result transfer (8 B per fetched rule slot) in the shared
                # ops basis (roofline.XFER_OPS_PER_BYTE, DESIGN.md §10)
                kf_est = (min(k * self.overfetch, n_rules)
                          if self.dedup_consequents else k)
                per_query = (float(n_rules) * state.W
                             + 8.0 * kf_est * XFER_OPS_PER_BYTE)
                work = per_query * max(len(batches[i]), 1)
                nfuse = self.controller.choose_fusion(
                    work_per_unit=work, queued=len(batches) - i,
                    max_fuse=self.max_fuse,
                    latency_budget_s=self.latency_budget_s)
                # uncalibrated: dispatch one batch — it is the calibration
                nfuse = 1 if nfuse is None else int(nfuse)
            else:
                prev = history[-1] if history else None
                prev2 = history[-2] if len(history) > 1 else None
                mode, val = self.policy.decide(prev, prev2)
                if mode == "width":
                    nfuse = int(val)
                else:  # budget_alpha: fuse ⌊α⌋ queued batches (α=1 ⇒
                       # per-batch, the drivers' "no widening" semantics)
                    nfuse = int(np.floor(val))
            nfuse = max(1, min(nfuse, self.max_fuse, len(batches) - i))
            group = batches[i:i + nfuse]
            sizes = [len(b) for b in group]
            flat = [pair for batch in group for pair in batch]

            t0 = time.perf_counter()
            with current_tracer().span(
                    "serve.engine_dispatch", n_batches=nfuse,
                    n_queries=len(flat), n_rules=n_rules,
                    impl=self.impl) as dspan:
                if flat:
                    kf = (min(k * self.overfetch, n_rules)
                          if self.dedup_consequents else k)
                    vals, idx = self._dispatch(state, state.pack(flat), kf)
                    decoded = self._decode(state, vals, idx, k)
                else:
                    decoded = []
            elapsed = time.perf_counter() - t0
            dspan.set(elapsed_seconds=elapsed)

            off = 0
            for sz in sizes:
                results.append(decoded[off:off + sz])
                off += sz
            n_q = len(flat)
            if self.controller is not None and n_q:
                self.controller.observe_serve(
                    float(n_rules) * state.W + 8.0 * kf * XFER_OPS_PER_BYTE,
                    n_q, elapsed)
            history.append(PhaseStats(n_rules * max(n_q, 1),
                                      max(n_q, 1), elapsed))
            records.append(RuleServeRecord(phase_idx, nfuse, n_q, elapsed))
            i += nfuse
            phase_idx += 1
        self.records = records
        return results, records

    def query(self, baskets, top_k: int | None = None,
              tenant: str | None = None):
        """Single-batch convenience: recommendations for one list of baskets
        (bare baskets or ``(tenant, basket)`` pairs)."""
        results, _ = self.serve([list(baskets)], top_k=top_k, tenant=tenant)
        return results[0]
