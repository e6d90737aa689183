"""Selectivity-driven query optimization (paper §4.3).

A semantic query is a conjunction of filter predicates, each evaluated by a
VLM call per surviving image. The optimizer orders filters ascending by
estimated selectivity (most selective first minimizes downstream calls); the
executor runs the cascade and accounts true VLM calls.

Runtime model: end-to-end seconds = estimation latency (measured) +
VLM_calls x per-call latency. The per-call constant defaults to the
v5e roofline-derived decode latency for qwen25-vl-7b (batched serving would
divide it; the paper's single-GPU ollama setting maps to sequential calls, so
relative overheads match the paper's protocol).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import numpy as np

from repro.core.estimators import Estimate
from repro.core.synthetic import Corpus
from repro.obs import spans

# ~0.15 s/call: 7B bf16 decode w/ short answer on one v5e host slice
# (2*7e9 FLOPs/token / (8 chips * 197e12) plus weight streaming; matches the
# order of the paper's A100 ollama latencies)
DEFAULT_VLM_CALL_S = 0.15


@dataclasses.dataclass
class QueryPlan:
    filter_order: list[int]           # node ids, most selective first
    estimates: list[Estimate]
    est_latency_s: float
    est_vlm_calls: float
    degraded: bool = False            # any estimate answered from bounds
    #                                   (its Estimate.extra carries the
    #                                   certified "sel_interval")
    # estimated selectivity of each cascade *prefix* (filters 0..i ANDed),
    # filled by the compound planner; None for independence-ordered plans
    prefix_sels: list[float] | None = None


class _CoalescedProbe:
    """Request-scoped probe callable: routes through the coalescer's
    control plane and keeps the per-predicate ``ProbeOutcome``s so the
    planner can mark bound-only (degraded) estimates afterwards."""

    def __init__(self, coalescer, deadline, degraded_ok):
        self.coalescer = coalescer
        self.deadline = deadline
        self.degraded_ok = degraded_ok
        self.outcomes = []

    def __call__(self, preds, thresholds):
        res = self.coalescer.probe_outcomes(
            preds, thresholds, deadline=self.deadline,
            degraded_ok=self.degraded_ok)
        self.outcomes.extend(res)
        return np.asarray([o.sel for o in res])


@dataclasses.dataclass
class ExecutionResult:
    plan: QueryPlan
    vlm_calls: int                    # true calls during cascade execution
    result_ids: np.ndarray
    exec_s: float                     # modeled: calls x per-call
    total_s: float                    # estimation + execution
    overhead_s: float = 0.0           # vs oracle plan (filled by caller)


def _mark_degraded(ests: list, outcomes: list) -> bool:
    """Map accumulated ``ProbeOutcome``s back onto per-filter estimates.

    The ensemble estimator may invoke the probe more than once per batch
    (e.g. a refinement pass), so ``outcomes`` holds one *group* of
    ``len(ests)`` outcomes per probe call, in filter order within each
    group. Filter ``j``'s outcomes are therefore ``outcomes[j::len(ests)]``
    — an estimate is degraded if ANY of its probe calls answered from
    bounds. An outcome count that is not a whole number of groups cannot
    be attributed to filters and raises (a silent skip here is exactly the
    bug this replaces: bound-only plans losing their ``degraded`` mark).
    """
    n_out, n_est = len(outcomes), len(ests)
    if n_out == 0:
        return False
    if n_est == 0 or n_out % n_est != 0:
        raise RuntimeError(
            f"cannot reconcile {n_out} probe outcome(s) with {n_est} "
            f"estimate(s): the probe wrapper saw batches that are not a "
            f"whole multiple of the filter count, so degraded/bound-only "
            f"status cannot be attributed per filter")
    degraded = False
    for j, e in enumerate(ests):
        for o in outcomes[j::n_est]:
            if o.degraded:
                degraded = True
                e.extra["degraded"] = True
                e.extra["sel_interval"] = (o.lo, o.hi)
    return degraded


def _compound_order(filters: list, ests: list, estimator, seed: int
                    ) -> tuple[list[int], list[float]] | None:
    """Greedy conditional ordering: pick the filter with the smallest
    marginal selectivity first, then repeatedly append the candidate that
    minimizes the *joint* selectivity of the extended prefix (one compound
    probe per candidate — nearly free through the joint cluster-bound
    pass). Returns (order indices, per-prefix joint selectivities), or
    None when any estimate lacks a calibrated threshold (the compound
    probe needs per-conjunct thresholds)."""
    thrs = [e.threshold for e in ests]
    if any(t is None for t in thrs):
        return None
    remaining = list(range(len(ests)))
    first = min(remaining, key=lambda i: (ests[i].selectivity, i))
    order = [first]
    remaining.remove(first)
    prefix_sels = [float(ests[first].selectivity)]
    while remaining:
        best, best_sel = None, None
        for c in remaining:
            ids = [filters[i] for i in order + [c]]
            ts = [thrs[i] for i in order + [c]]
            sel = float(estimator.compound_selectivity(ids, ts, seed=seed))
            if best_sel is None or sel < best_sel:
                best, best_sel = c, sel
        order.append(best)
        remaining.remove(best)
        prefix_sels.append(best_sel)
    return order, prefix_sels


def plan_query(filters: Sequence[int], estimator, seed: int = 0,
               coalescer=None, *, deadline_ms: float | None = None,
               degraded_ok: bool | None = None,
               compound: bool = False) -> QueryPlan:
    """Estimate every filter, order ascending by selectivity.

    Fast path: estimators exposing ``estimate_batch`` (specificity, kv-batch,
    ensemble) get all filters of the query in one call — thresholds batched
    on-device, selectivities from a single batched histogram probe (one store
    pass). Estimators without it fall back to the per-filter loop.

    Serving path: pass a ``repro.launch.coalescer.PredicateCoalescer``
    handle and estimators advertising ``supports_probe`` route their probe
    through it — concurrent ``plan_query`` calls then share one cross-query
    micro-batched store pass, and hot predicates resolve from its LRU cache
    without probing at all.

    Control plane: ``deadline_ms`` (wall budget for this plan's probes,
    absolute from entry; None defers to the coalescer's config) and
    ``degraded_ok`` (accept certified bound-only answers instead of errors
    under overload/faults) are forwarded per request. A plan built from any
    degraded estimate is marked ``QueryPlan.degraded`` and each such
    estimate carries ``extra['sel_interval'] = (lo, hi)`` — the cascade
    order is then a best-effort order over interval midpoints.

    Compound planning: with ``compound=True`` and an estimator exposing
    ``compound_selectivity`` (the ensemble), multi-filter plans are ordered
    by *conditional* selectivity — greedy joint-prefix probes through the
    index's joint cluster-bound pass — instead of the independence
    assumption; ``QueryPlan.prefix_sels`` then carries the estimated joint
    selectivity of every cascade prefix. Degraded (bound-only) plans keep
    the interval-midpoint order: a compound probe cannot certify bounds."""
    with spans.plan_span(len(filters)):
        return _plan_query(filters, estimator, seed, coalescer,
                           deadline_ms, degraded_ok, compound)


def _plan_query(filters, estimator, seed, coalescer, deadline_ms,
                degraded_ok, compound) -> QueryPlan:
    t0 = time.perf_counter()
    batch = getattr(estimator, "estimate_batch", None)
    wrapper = None
    if batch is not None and len(filters) > 0:
        kwargs = {}
        if coalescer is not None and getattr(estimator, "supports_probe",
                                             False):
            if hasattr(coalescer, "probe_outcomes"):
                deadline = (time.monotonic() + deadline_ms / 1e3
                            if deadline_ms else None)
                wrapper = _CoalescedProbe(coalescer, deadline, degraded_ok)
                kwargs["probe"] = wrapper
            else:
                kwargs["probe"] = coalescer.selectivity_batch
        ests = batch(list(filters), seed=seed, **kwargs)
    else:
        ests = [estimator.estimate(f, seed=seed) for f in filters]
    degraded = False
    if wrapper is not None:
        degraded = _mark_degraded(ests, wrapper.outcomes)
    filters = list(filters)
    order = list(np.argsort([e.selectivity for e in ests], kind="stable"))
    prefix_sels = None
    if (compound and not degraded and len(ests) > 1
            and hasattr(estimator, "compound_selectivity")):
        ordered = _compound_order(filters, ests, estimator, seed)
        if ordered is not None:
            order, prefix_sels = ordered
    est_s = sum(e.measured_s for e in ests)
    calls = sum(e.vlm_calls for e in ests)
    return QueryPlan(
        filter_order=[filters[i] for i in order],
        estimates=[ests[i] for i in order],
        est_latency_s=est_s,
        est_vlm_calls=calls,
        degraded=degraded,
        prefix_sels=prefix_sels,
    )


def execute_cascade(
    corpus: Corpus, plan: QueryPlan, *, seed: int = 0,
    per_call_s: float = DEFAULT_VLM_CALL_S,
    obs=None, est_name: str | None = None, feedback=None,
) -> ExecutionResult:
    """Run the cascade; with ``obs`` (a ``repro.obs.ObsHub``), feed the
    now-known true selectivities back as per-estimator q-error accounting
    (``obs.record_plan``) — execution makes ground truth free, the
    observation behind Larch-style learned feedback (PAPERS.md).

    ``feedback`` (duck-typed, e.g. the ensemble estimator with feedback
    enabled) receives ``observe(corpus, plan, observed_prefix)`` after the
    cascade: the observed per-prefix survival fractions (padded with 0.0
    past an early empty-set break — the prefix truly matched nothing)
    plus ground-truth per-filter selectivities, which it writes back into
    its correction weights and observed-selectivity cache."""
    n0 = len(corpus.images)
    alive = np.arange(n0)
    calls = 0
    observed_prefix: list[float] = []
    for f in plan.filter_order:
        if len(alive) == 0:
            observed_prefix.append(0.0)
            continue
        ans = corpus.vlm_answer(f, alive, seed=seed)
        calls += len(alive)
        alive = alive[ans]
        observed_prefix.append(len(alive) / max(n0, 1))
    exec_s = calls * per_call_s
    est_exec_s = plan.est_vlm_calls * per_call_s
    total = plan.est_latency_s + est_exec_s + exec_s
    if obs is not None:
        obs.record_plan(est_name or "estimator", corpus, plan,
                        observed_prefix=observed_prefix)
    if feedback is not None:
        feedback.observe(corpus, plan, observed_prefix, seed=seed)
    return ExecutionResult(plan=plan, vlm_calls=calls, result_ids=alive,
                           exec_s=exec_s, total_s=total)


def run_query(corpus, filters, estimator, *, seed=0,
              per_call_s: float = DEFAULT_VLM_CALL_S, coalescer=None,
              deadline_ms: float | None = None,
              degraded_ok: bool | None = None, obs=None,
              est_name: str | None = None, compound: bool = False,
              feedback=None) -> ExecutionResult:
    """Plan + execute one query, forwarding the full control plane: the
    coalescer / deadline / degraded knobs reach ``plan_query`` and the
    telemetry + feedback handles reach ``execute_cascade`` (previously
    dropped here, so wrapped plans never hit ``obs.record_plan``)."""
    plan = plan_query(filters, estimator, seed=seed, coalescer=coalescer,
                      deadline_ms=deadline_ms, degraded_ok=degraded_ok,
                      compound=compound)
    return execute_cascade(corpus, plan, seed=seed, per_call_s=per_call_s,
                           obs=obs, est_name=est_name, feedback=feedback)


def generate_queries(corpus: Corpus, *, n_queries: int, n_filters: int,
                     seed: int = 0) -> list[list[int]]:
    """Random conjunctions over the available predicates (paper: 100 each of
    2/3/4 filters). ``n_filters`` must not exceed the corpus's predicate
    count — conjunctions sample without replacement."""
    rng = np.random.default_rng(seed)
    preds = corpus.predicate_nodes()
    if n_filters < 1:
        raise ValueError(f"n_filters must be >= 1, got {n_filters}")
    if n_filters > len(preds):
        raise ValueError(
            f"n_filters={n_filters} exceeds the corpus's "
            f"{len(preds)} predicate node(s); conjunctions sample "
            f"predicates without replacement")
    return [list(rng.choice(preds, size=n_filters, replace=False))
            for _ in range(n_queries)]
