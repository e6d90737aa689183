"""Serving control plane (PR 6): deadlines, admission control, retry +
circuit breaker around probe dispatch, bound-only graceful degradation, and
flusher-death propagation — exercised by the deterministic chaos harness.

The load-bearing invariants:

  * reconciliation — every request resolves into exactly one bucket:
    ``requests == probe_scored + cache_hits + coalesced_dups + shed
    + degraded + errors`` (asserted after every scenario, faulty or not);
  * no hangs — a dead flusher or a blown deadline fails/degrades waiters
    promptly instead of blocking on ``event.wait`` forever;
  * degraded never wrong — bound-only answers are certified intervals that
    contain the true selectivity (cluster-index Cauchy-Schwarz bounds).
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.histogram import SemanticHistogram
from repro.core.synthetic import clustered_unit_vectors
from repro.index import build_clustered_store, build_sharded_clustered_store
from repro.launch.chaos import (
    ChaosConfig,
    ChaosInjector,
    ChaosProbeError,
    FlusherKill,
)
from repro.launch.coalescer import (
    BreakerOpenError,
    CoalescerConfig,
    DeadlineExceededError,
    FlusherDiedError,
    PredicateCoalescer,
    ProbeOutcome,
    ShedError,
)
from repro.runtime.fault_tolerance import (
    CircuitBreaker,
    RetryPolicy,
    TransientError,
)


def _unit_rows(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _assert_reconciles(st):
    resolved = (st["probe_scored"] + st["cache_hits"] + st["coalesced_dups"]
                + st["shed"] + st["degraded"] + st["errors"])
    assert st["requests"] == resolved, st


def _wait_until(cond, timeout=10.0):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition never became true")
        time.sleep(0.002)


# ----------------------------------------------------------- config / spec


def test_coalescer_config_validates_up_front():
    for bad in (dict(max_batch=0), dict(window_ms=0.0),
                dict(cache_capacity=0), dict(max_queue=-1),
                dict(max_pending_age_ms=-0.1), dict(deadline_ms=-5.0)):
        with pytest.raises(ValueError):
            CoalescerConfig(**bad)
    cfg = CoalescerConfig()         # robustness knobs default off
    assert cfg.max_queue == 0 and cfg.deadline_ms == 0.0
    assert not cfg.degraded_ok


def test_chaos_spec_parses_and_validates():
    cfg = ChaosConfig.parse("seed=3,fail=0.25,delay=0.5,delay-ms=7,kill-at=2")
    assert cfg == ChaosConfig(seed=3, fail_rate=0.25, delay_rate=0.5,
                              delay_ms=7.0, kill_flusher_at=2)
    assert ChaosConfig.parse("") == ChaosConfig()
    with pytest.raises(ValueError, match="unknown chaos key"):
        ChaosConfig.parse("frobnicate=1")
    with pytest.raises(ValueError, match="key=value"):
        ChaosConfig.parse("fail")
    with pytest.raises(ValueError, match="fail_rate"):
        ChaosConfig.parse("fail=1.5")


def test_chaos_injection_is_deterministic_per_seed():
    def ok():
        return "ok"

    def run(seed):
        inj = ChaosInjector(ChaosConfig(seed=seed, fail_rate=0.5))
        fn = inj.wrap(ok)
        res = []
        for _ in range(32):
            try:
                res.append(fn() == "ok")
            except ChaosProbeError:
                res.append(False)
        return res, inj.stats()

    a, sa = run(11)
    b, sb = run(11)
    c, _ = run(12)
    assert a == b and sa == sb          # pure function of the seed
    assert a != c                       # and the seed actually matters
    assert sa["injected_failures"] == a.count(False)


# ----------------------------------------------------- certified bounds


def test_clustered_count_bounds_contain_true_counts(rng):
    x, _ = clustered_unit_vectors(2000, 32, n_centers=8, spread=0.2, seed=0)
    cs = build_clustered_store(x, 16, iters=4, seed=0, impl="xla")
    hist = SemanticHistogram(jnp.asarray(x))
    preds = x[[3, 700, 1500]]
    thrs = np.asarray([0.3, 0.6, 1.0], np.float32)
    lo, hi = cs.count_bounds(preds, thrs)
    assert lo.shape == hi.shape == (3, 1)
    assert (lo <= hi).all() and (lo >= 0).all() and (hi <= len(x)).all()
    for i in range(3):
        true = hist.count_within(preds[i], float(thrs[i]))
        assert lo[i, 0] <= true <= hi[i, 0], (i, lo[i, 0], true, hi[i, 0])
    # the bounds must do better than the trivial [0, N] somewhere, or the
    # degraded answers carry no information
    assert (lo > 0).any() or (hi < len(x)).any()


def test_sharded_count_bounds_sum_per_shard(rng):
    x, _ = clustered_unit_vectors(1200, 32, n_centers=8, spread=0.2, seed=1)
    sidx = build_sharded_clustered_store(x, 8, 2, iters=4, seed=0,
                                         impl="xla")
    hist = SemanticHistogram(jnp.asarray(x))
    preds = x[[10, 600]]
    thrs = np.asarray([0.5, 0.9], np.float32)
    lo, hi = sidx.count_bounds(preds, thrs)
    per = [s.count_bounds(preds, thrs) for s in sidx.shards]
    assert (lo == sum(p[0] for p in per)).all()
    assert (hi == sum(p[1] for p in per)).all()
    for i in range(2):
        true = hist.count_within(preds[i], float(thrs[i]))
        assert lo[i, 0] <= true <= hi[i, 0]


def test_selectivity_bounds_with_and_without_index(rng):
    x, _ = clustered_unit_vectors(1500, 32, n_centers=8, spread=0.2, seed=2)
    cs = build_clustered_store(x, 12, iters=4, seed=0, impl="xla")
    indexed = SemanticHistogram(jnp.asarray(x), index=cs)
    plain = SemanticHistogram(jnp.asarray(x))
    preds = x[[5, 900]]
    thrs = np.asarray([0.4, 0.8], np.float32)
    lo, hi = indexed.selectivity_bounds(preds, thrs)
    true = plain.selectivity_batch(preds, thrs)
    assert (0.0 <= lo).all() and (hi <= 1.0).all()
    assert (lo <= true + 1e-12).all() and (true <= hi + 1e-12).all()
    # no index -> trivial but still correct interval
    lo0, hi0 = plain.selectivity_bounds(preds, thrs)
    assert (lo0 == 0.0).all() and (hi0 == 1.0).all()


# ------------------------------------------------- flusher-death handling


def test_flusher_death_fails_waiters_and_restarts(rng):
    """The 60s-hang regression: a flusher killed mid-window must fail its
    waiters immediately (FlusherDiedError), then a fresh flusher serves
    the next request."""
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(kill_flusher_at=1))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=1, window_ms=10),
            chaos=chaos) as coal:
        t0 = time.monotonic()
        with pytest.raises(FlusherDiedError):
            coal.selectivity(x[0], 0.8)
        assert time.monotonic() - t0 < 10, "waiter must not hang"
        # replacement flusher: next request is served exactly
        sel = coal.selectivity(x[1], 0.8)
        st = coal.stats()
    assert sel == pytest.approx(hist.selectivity(x[1], 0.8), abs=1e-9)
    assert st["flusher_deaths"] == 1 and st["flusher_restarts"] == 1
    assert st["errors"] == 1 and st["probe_scored"] == 1
    assert st["chaos"]["injected_kills"] == 1
    _assert_reconciles(st)


def test_flusher_death_mid_window_fails_all_waiters(rng):
    """Every waiter of the killed window resolves promptly — including
    piggybacked threads that never created an entry."""
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(kill_flusher_at=1))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=3, window_ms=10_000),
            chaos=chaos) as coal:
        outcomes = {}

        def worker(i):
            try:
                coal.selectivity(x[i], 0.8)
                outcomes[i] = "value"
            except FlusherDiedError:
                outcomes[i] = "died"

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        elapsed = time.monotonic() - t0
        st = coal.stats()
    assert elapsed < 25, "death must propagate, not wait out any timeout"
    assert [outcomes[i] for i in range(3)] == ["died"] * 3
    assert st["errors"] == 3 and st["flusher_deaths"] == 1
    _assert_reconciles(st)


def test_flusher_death_with_degraded_ok_answers_from_bounds(rng):
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(kill_flusher_at=1))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=1, window_ms=10),
            chaos=chaos) as coal:
        (o,) = coal.probe_outcomes(x[:1], np.asarray([0.8]),
                                   degraded_ok=True)
        st = coal.stats()
    assert o.degraded and o.lo == 0.0 and o.hi == 1.0   # no index: trivial
    assert o.lo <= o.sel <= o.hi
    assert st["degraded"] == 1 and st["errors"] == 0
    _assert_reconciles(st)


# -------------------------------------------------- deadlines & admission


def test_deadline_degrades_to_bounds_instead_of_waiting(rng):
    """An 800ms injected probe delay vs an 80ms deadline: the caller gets
    certified bounds promptly, and they contain the truth."""
    x, _ = clustered_unit_vectors(1000, 32, n_centers=8, spread=0.2, seed=3)
    cs = build_clustered_store(x, 12, iters=4, seed=0, impl="xla")
    hist = SemanticHistogram(jnp.asarray(x), index=cs)
    plain = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(delay_rate=1.0, delay_ms=800.0))
    preds = x[:2]
    thrs = np.asarray([0.5, 0.9], np.float32)
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=2, window_ms=10),
            chaos=chaos) as coal:
        t0 = time.monotonic()
        outs = coal.probe_outcomes(
            preds, thrs, deadline=time.monotonic() + 0.08, degraded_ok=True)
        elapsed = time.monotonic() - t0
        st = coal.stats()
    assert elapsed < 0.6, "deadline must cut the wait, not ride out 800ms"
    true = plain.selectivity_batch(preds, thrs)
    for o, t in zip(outs, true):
        assert o.degraded
        assert o.lo - 1e-12 <= t <= o.hi + 1e-12
        assert o.lo <= o.sel <= o.hi
    assert st["degraded"] == 2
    _assert_reconciles(st)


def test_deadline_without_degraded_ok_raises_and_reconciles(rng):
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(delay_rate=1.0, delay_ms=800.0))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=2, window_ms=10),
            chaos=chaos) as coal:
        with pytest.raises(DeadlineExceededError):
            coal.probe_outcomes(x[:2], np.full(2, 0.8, np.float32),
                                deadline=time.monotonic() + 0.05)
        _wait_until(lambda: coal.stats()["errors"] == 2)
        st = coal.stats()
    # the raise counts itself AND the abandoned second wait
    assert st["errors"] == 2 and st["requests"] == 2
    _assert_reconciles(st)


def test_admission_control_sheds_over_watermark(rng):
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=64, window_ms=10_000,
                                  max_queue=1)) as coal:
        done = []
        t = threading.Thread(target=lambda: done.append(
            coal.selectivity(x[0], 0.8)))
        t.start()
        _wait_until(lambda: coal.stats()["queue_depth_hwm"] == 1)
        # queue is at the watermark: bound answer when tolerated ...
        (o,) = coal.probe_outcomes(x[1:2], np.asarray([0.8]),
                                   degraded_ok=True)
        assert o.degraded
        # ... hard ShedError when not
        with pytest.raises(ShedError):
            coal.probe_outcomes(x[2:3], np.asarray([0.8]))
        coal.flush_now()
        t.join(timeout=30)
        st = coal.stats()
    assert done and done[0] == pytest.approx(
        hist.selectivity(x[0], 0.8), abs=1e-9)
    assert st["shed"] == 2 and st["queue_depth_hwm"] == 1
    assert st["probe_scored"] == 1
    _assert_reconciles(st)


def test_unreachable_deadline_sheds_without_queueing(rng):
    """If the flush-latency EWMA says the probe cannot land in time, the
    request is shed at admission instead of queueing doomed work."""
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=4, window_ms=10)) as coal:
        coal.watchdog.ewma_s = 10.0     # pretend flushes take 10s
        (o,) = coal.probe_outcomes(x[:1], np.asarray([0.8]),
                                   deadline=time.monotonic() + 0.05,
                                   degraded_ok=True)
        st = coal.stats()
    assert o.degraded
    assert st["shed"] == 1 and st["probes_fired"] == 0
    _assert_reconciles(st)


# ------------------------------------------------------- retry & breaker


def test_transient_probe_failures_are_retried(rng):
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    orig = hist.probe_batch
    state = {"left": 2}

    def flaky(*a, **kw):
        if state["left"] > 0:
            state["left"] -= 1
            raise TransientError("flaky dependency")
        return orig(*a, **kw)

    hist.probe_batch = flaky
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=1, window_ms=10),
            retry=RetryPolicy(max_retries=2, base_delay_s=0.001)) as coal:
        sel = coal.selectivity(x[0], 0.8)
        st = coal.stats()
    hist.probe_batch = orig
    assert sel == pytest.approx(hist.selectivity(x[0], 0.8), abs=1e-9)
    assert st["retries"] == 2 and st["probe_failures"] == 2
    assert st["probes_fired"] == 1 and st["errors"] == 0
    _assert_reconciles(st)


def test_breaker_trips_fast_fails_then_recovers(rng):
    x = _unit_rows(rng, 300, 32)
    hist = SemanticHistogram(jnp.asarray(x))
    orig = hist.probe_batch
    state = {"boom": True}

    def flaky(*a, **kw):
        if state["boom"]:
            raise TransientError("dependency down")
        return orig(*a, **kw)

    hist.probe_batch = flaky
    clk = {"t": 0.0}
    breaker = CircuitBreaker(failure_threshold=2, cooldown_s=5.0,
                             clock=lambda: clk["t"])
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=1, window_ms=10),
            retry=RetryPolicy(max_retries=0),
            breaker=breaker) as coal:
        # two failed windows trip the breaker open
        for i in range(2):
            with pytest.raises(TransientError):
                coal.selectivity(x[i], 0.8)
        assert breaker.stats()["state"] == "open"
        # open breaker: fast-fail without touching the probe path
        (o,) = coal.probe_outcomes(x[2:3], np.asarray([0.8]),
                                   degraded_ok=True)
        assert o.degraded
        with pytest.raises(BreakerOpenError):
            coal.probe_outcomes(x[3:4], np.asarray([0.8]))
        # cooldown elapses + dependency heals -> half-open trial closes it
        clk["t"] = 10.0
        state["boom"] = False
        sel = coal.selectivity(x[4], 0.8)
        st = coal.stats()
    hist.probe_batch = orig
    assert sel == pytest.approx(hist.selectivity(x[4], 0.8), abs=1e-9)
    assert st["breaker"]["state"] == "closed"
    assert st["breaker"]["opens"] == 1
    assert st["breaker_fastfails"] == 2
    assert st["degraded"] == 1 and st["errors"] == 3
    assert st["probe_scored"] == 1
    _assert_reconciles(st)


# ----------------------------------------------------- planner integration


def test_plan_query_marks_degraded_plans(rng):
    from repro.core.optimizer import plan_query
    from repro.core.synthetic import make_corpus
    from tests.test_coalescer import _spec_estimator

    c = make_corpus("wildlife", n_images=400, seed=0)
    hist = SemanticHistogram(jnp.asarray(c.images))
    est = _spec_estimator(c, hist)
    filters = c.predicate_nodes()[:3]
    chaos = ChaosInjector(ChaosConfig(delay_rate=1.0, delay_ms=500.0))
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=3, window_ms=10),
            chaos=chaos) as coal:
        t0 = time.monotonic()
        plan = plan_query(filters, est, seed=0, coalescer=coal,
                          deadline_ms=40.0, degraded_ok=True)
        elapsed = time.monotonic() - t0
    assert elapsed < 2.0
    assert plan.degraded
    for e in plan.estimates:
        assert e.extra.get("degraded") is True
        lo, hi = e.extra["sel_interval"]
        assert 0.0 <= lo <= hi <= 1.0
    # chaos off: plans are never marked degraded (bitwise PR-5 behavior)
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=3, window_ms=10)) as coal:
        plan2 = plan_query(filters, est, seed=0, coalescer=coal)
    assert not plan2.degraded
    assert all("sel_interval" not in e.extra for e in plan2.estimates)


# -------------------------------------------------------- chaos scenarios


@pytest.mark.chaos
def test_chaos_reconciliation_under_injected_failures(rng):
    """8 threads x 3 predicates through a 40%-failure probe path: every
    request resolves, counters reconcile exactly, exact answers equal the
    plain-histogram truth, degraded intervals contain it."""
    x, _ = clustered_unit_vectors(500, 32, n_centers=10, spread=0.2, seed=4)
    cs = build_clustered_store(x, 10, iters=4, seed=0, impl="xla")
    hist = SemanticHistogram(jnp.asarray(x), index=cs)
    plain = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(seed=7, fail_rate=0.4))
    n_threads, per = 8, 3
    thr = np.full(per, 0.8, np.float32)
    outs = {}
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=8, window_ms=20,
                                  degraded_ok=True),
            chaos=chaos,
            retry=RetryPolicy(max_retries=1, base_delay_s=0.001)) as coal:

        def worker(i):
            outs[i] = coal.probe_outcomes(x[per * i:per * (i + 1)], thr)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        st = coal.stats()

    assert len(outs) == n_threads, "a worker never resolved (hang/drop)"
    true = plain.selectivity_batch(x[:n_threads * per],
                                   np.full(n_threads * per, 0.8, np.float32))
    n_degraded = 0
    for i in range(n_threads):
        for j, o in enumerate(outs[i]):
            assert isinstance(o, ProbeOutcome)
            t = true[per * i + j]
            if o.degraded:
                n_degraded += 1
                assert o.lo - 1e-12 <= t <= o.hi + 1e-12
            else:
                assert o.sel == pytest.approx(t, abs=1e-9)
    assert st["requests"] == n_threads * per
    assert st["errors"] == 0            # degraded_ok: nothing raises
    assert st["degraded"] == n_degraded
    assert st["chaos"]["injected_failures"] >= 1, "chaos must actually bite"
    _assert_reconciles(st)


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_sweep_is_hang_free_and_lossless(rng):
    """The full storm — failures + delays + a flusher kill — under config
    deadlines and degraded_ok: every call returns within deadline + grace,
    zero requests silently dropped, counters reconcile, intervals contain
    the oracle truth."""
    x, _ = clustered_unit_vectors(1000, 32, n_centers=10, spread=0.2,
                                  seed=5)
    cs = build_clustered_store(x, 12, iters=4, seed=0, impl="xla")
    hist = SemanticHistogram(jnp.asarray(x), index=cs)
    plain = SemanticHistogram(jnp.asarray(x))
    chaos = ChaosInjector(ChaosConfig(seed=1, fail_rate=0.3, delay_rate=0.3,
                                      delay_ms=30.0, kill_flusher_at=5))
    n_threads, calls, per = 8, 4, 2
    deadline_s, grace_s = 0.5, 2.0
    results: dict[tuple, list] = {}
    slow_calls = []
    with PredicateCoalescer(
            hist, CoalescerConfig(max_batch=8, window_ms=20,
                                  deadline_ms=deadline_s * 1e3,
                                  degraded_ok=True),
            chaos=chaos,
            retry=RetryPolicy(max_retries=1, base_delay_s=0.001)) as coal:

        def worker(i):
            for c in range(calls):
                base = (i * calls + c) * per
                t0 = time.monotonic()
                outs = coal.probe_outcomes(
                    x[base:base + per], np.full(per, 0.8, np.float32))
                dt = time.monotonic() - t0
                if dt > deadline_s + grace_s:
                    slow_calls.append((i, c, dt))
                results[(i, c)] = outs

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        st = coal.stats()

    assert not slow_calls, f"calls blew deadline + grace: {slow_calls}"
    assert len(results) == n_threads * calls, "dropped calls"
    n = n_threads * calls * per
    true = plain.selectivity_batch(x[:n], np.full(n, 0.8, np.float32))
    for (i, c), outs in results.items():
        assert len(outs) == per and all(o is not None for o in outs)
        for j, o in enumerate(outs):
            t = true[(i * calls + c) * per + j]
            if o.degraded:
                assert o.lo - 1e-12 <= t <= o.hi + 1e-12
            else:
                assert o.sel == pytest.approx(t, abs=1e-9)
    assert st["requests"] == n
    assert st["errors"] == 0
    assert st["flusher_deaths"] >= 1, "the kill-at=5 launch must have fired"
    assert st["flusher_restarts"] >= 1
    _assert_reconciles(st)


@pytest.mark.chaos
def test_chaos_storm_with_full_telemetry_reconciles(rng, tmp_path):
    """PR 8: the storm (failures + a flusher kill + restart) with the
    registry AND a sample=1 tracer attached — the legacy ``stats()``
    dict, the registry counters, the per-resolution submit-span counts,
    and the JSONL summary record must all agree EXACTLY, and exact
    answers must stay bitwise equal to an untraced run."""
    import json

    from repro.obs import ObsHub, Tracer

    x, _ = clustered_unit_vectors(600, 32, n_centers=10, spread=0.2, seed=6)
    cs = build_clustered_store(x, 10, iters=4, seed=0, impl="xla")
    n_threads, per = 6, 3
    thr = np.full(per, 0.8, np.float32)

    def storm(obs):
        hist = SemanticHistogram(jnp.asarray(x), index=cs)
        chaos = ChaosInjector(ChaosConfig(seed=9, fail_rate=0.3,
                                          kill_flusher_at=2))
        outs = {}
        with PredicateCoalescer(
                hist, CoalescerConfig(max_batch=6, window_ms=20,
                                      degraded_ok=True),
                chaos=chaos,
                retry=RetryPolicy(max_retries=1, base_delay_s=0.001),
                obs=obs) as coal:

            def worker(i):
                outs[i] = coal.probe_outcomes(
                    x[per * i:per * (i + 1)], thr)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            # kill fired? then the restart must have been counted too
            st = coal.stats()
        return outs, st

    path = str(tmp_path / "storm.jsonl")
    tr = Tracer(path, sample=1)
    hub = ObsHub(tracer=tr)
    outs, st = storm(hub)
    hub.write_trace_summary(st)
    tr.close()

    assert len(outs) == n_threads
    assert st["requests"] == n_threads * per
    assert st["errors"] == 0                    # degraded_ok: no raises
    _assert_reconciles(st)
    if st["flusher_deaths"]:
        assert st["flusher_restarts"] >= 1

    # 1. registry counters == legacy stats() buckets (one source of truth)
    counters = hub.registry.snapshot()["counters"]
    for name in ("requests", "probe_scored", "cache_hits",
                 "coalesced_dups", "shed", "degraded", "errors",
                 "retries", "probe_failures", "flusher_deaths",
                 "flusher_restarts", "probes_fired"):
        assert counters[f"coalescer.{name}"] == st[name], name

    # 2. sample=1 submit spans partition requests exactly like counters
    sub = tr.submit_counts()
    assert sum(sub.values()) == st["requests"]
    for bucket, count in sub.items():
        assert st[bucket] == count, (bucket, sub, st)

    # 3. the JSONL summary record carries the same totals + span counts
    recs = [json.loads(line) for line in open(path)]
    summary = recs[-1]
    assert summary["kind"] == "summary"
    for name in ("requests", "probe_scored", "cache_hits",
                 "coalesced_dups", "shed", "degraded", "errors"):
        assert summary[name] == st[name], name
    n_submit = sum(1 for r in recs if r["kind"] == "submit")
    assert n_submit == st["requests"]
    assert summary["spans"].get("submit", 0) == n_submit
    # chaos injections surfaced as events on the same stream
    if st["chaos"]["injected_failures"]:
        assert counters.get("events.chaos_fail", 0) \
            == st["chaos"]["injected_failures"]
    if st["flusher_deaths"]:
        assert counters["events.flusher_death"] == st["flusher_deaths"]

    # 4. bitwise parity under faults: a *sequential* storm (so batch
    # composition — and with it each seeded per-launch injection — is
    # deterministic) resolves identically with telemetry on and off
    def seq_storm(obs):
        hist = SemanticHistogram(jnp.asarray(x), index=cs)
        chaos = ChaosInjector(ChaosConfig(seed=9, fail_rate=0.5,
                                          kill_flusher_at=2))
        with PredicateCoalescer(
                hist, CoalescerConfig(max_batch=per, window_ms=20,
                                      degraded_ok=True),
                chaos=chaos, retry=RetryPolicy(max_retries=0),
                obs=obs) as coal:
            outs = [coal.probe_outcomes(x[per * i:per * (i + 1)], thr)
                    for i in range(4)]
            return ([(o.sel, o.lo, o.hi, o.degraded)
                     for batch in outs for o in batch], coal.stats())

    tr2 = Tracer(str(tmp_path / "seq.jsonl"), sample=1)
    traced, st_a = seq_storm(ObsHub(tracer=tr2))
    tr2.close()
    plain, st_b = seq_storm(None)
    assert traced == plain, "results diverged under telemetry"
    assert any(d for *_, d in traced), "chaos must actually degrade some"
    for name in ("requests", "probe_scored", "degraded", "errors"):
        assert st_a[name] == st_b[name], name


def test_serve_main_exits_nonzero_when_queries_fail(monkeypatch):
    """Without --chaos every query must plan: a run whose probes all fail
    exits non-zero instead of counting the failures and returning."""
    from repro.launch import serve

    def failing_plan(*a, **kw):
        raise RuntimeError("probe failed")

    monkeypatch.setattr(serve, "plan_query", failing_plan)
    monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
    with pytest.raises(SystemExit) as exc:
        serve.main(["--concurrency", "2", "--queries", "2", "--filters", "2",
                    "--passes", "1", "--n-images", "300"])
    assert "2 queries failed" in str(exc.value.code)
