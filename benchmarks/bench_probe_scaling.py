"""Histogram-probe scaling: the paper's store at pod scale.

Demonstrates (a) measured single-device scan throughput vs N, (b) the
batched multi-predicate probe's amortization — one (N, d) x (d, B) pass for
B predicates vs B matvecs, reported as amortized µs/predicate and effective
per-predicate scan bandwidth at B ∈ {1, 8, 32, 128} — (c) the serving
layer: cross-query coalescing (one probe for G concurrent queries' filters
vs one probe per query) and the LRU predicate cache on a hot workload
(repeated predicates skip the scan entirely), (d) the cluster-pruned index:
scan fraction + speedup vs selectivity on a clustered store (exact counts,
sublinear rows at low selectivity), (d') compound conjunction probes — one
joint-bound pass for B correlated predicates, bitwise equal to the composed
full scan — (e) the sharded-probe collective
cost model: counts/top-k combine is O(B*k), so probe latency stays flat as
the store scales across chips (DESIGN.md §2), and (f) boundary-mass-
balanced index builds: on a Zipf-skewed grouped store, contiguous shard
blocks concentrate one concept's boundary rows on a few shards and every
probe pays the max — the balanced+split build packs clusters onto shards
by boundary mass, so the max per-shard boundary rows (and measured probe
wall time) drop, counts and top-k bitwise unchanged.

CSV: bench,config,us_per_call,derived

Every run also persists the rows machine-readably to
``BENCH_probe_scaling.json`` at the repo root (rows + config + git sha),
so the perf trajectory stays trackable across PRs.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

# self-bootstrapping: `python benchmarks/bench_probe_scaling.py` works
# without the PYTHONPATH=src:. incantation
_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [p for p in (str(_ROOT), str(_ROOT / "src"))
                if p not in sys.path]

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.analysis.roofline import V5E, peaks
HBM_BW, LINK_BW = peaks(V5E).hbm_bw, peaks(V5E).link_bw
from repro.core.histogram import _local_probe, _local_probe_batch


# child for the sharded-pruned section: 4 forced host devices, sharded
# full-scan vs sharded per-shard-pruned probes over the same clustered store
_SHARDED_CHILD = """
import time
import numpy as np
import jax.numpy as jnp
from repro.core.histogram import SemanticHistogram
from repro.core.synthetic import clustered_unit_vectors
from repro.index import build_sharded_clustered_store
from repro.launch.mesh import make_probe_mesh

n, d, k_shard, s = 100_000, 256, 160, 4     # K ~ sqrt(n/s) per shard
xc, _ = clustered_unit_vectors(n, d, n_centers=64, spread=0.25, seed=0)
mesh = make_probe_mesh(s)
t0 = time.perf_counter()
sidx = build_sharded_clustered_store(xc, k_shard, s, iters=6, seed=0,
                                     impl="xla")
build_s = time.perf_counter() - t0
print(f"ROW|probe_sharded_index_build|N={n},S={s},K={k_shard}/shard|"
      f"{build_s*1e6:.0f}|per-shard kmeans+reorder+radii")
full = SemanticHistogram(jnp.asarray(xc), mesh=mesh)
pruned = SemanticHistogram(jnp.asarray(xc), mesh=mesh, index=sidx)
pred = xc[17]
ds = np.sort(1.0 - xc @ pred)
for sel in (0.001, 0.01, 0.1):
    kth = max(1, int(sel * n))
    thr = float(0.5 * (ds[kth - 1] + ds[kth]))
    c_full = full.count_within(pred, thr)      # warm + reference
    sidx.reset_stats()
    c_prn = pruned.count_within(pred, thr)     # warm pruned shapes
    assert c_full == c_prn, (sel, c_full, c_prn)
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        full.count_within(pred, thr)
    full_us = (time.perf_counter() - t0) / iters * 1e6
    t0 = time.perf_counter()
    for _ in range(iters):
        pruned.count_within(pred, thr)
    prn_us = (time.perf_counter() - t0) / iters * 1e6
    st = sidx.stats()
    per = [p["scan_fraction"] for p in st["per_shard"]]
    print(f"ROW|probe_sharded_pruned_cpu|N={n},S={s},sel={sel:.1%}|"
          f"{prn_us:.0f}|scan_frac={st['scan_fraction']:.1%},"
          f"shard_spread={min(per):.1%}..{max(per):.1%},"
          f"full={full_us:.0f}us,speedup={full_us/prn_us:.1f}x,"
          f"count_diff={c_full - c_prn}")
"""


# child for the boundary-balanced build section (PR 5): a Zipf-skewed
# *grouped* store (head concept's rows contiguous, the ingest order real
# stores have) over 4 host shards — the contiguous build concentrates the
# head concept's boundary rows on the shards that hold it, the
# balanced+split build packs clusters onto shards by boundary mass.
# Acceptance: balanced max per-shard boundary rows < contiguous (and probe
# wall time drops) at <= 1% selectivity, count_diff=0, bitwise top-k.
_BALANCED_CHILD = """
import time
import numpy as np
import jax.numpy as jnp
from repro.core.histogram import SemanticHistogram
from repro.core.synthetic import clustered_unit_vectors
from repro.index import build_sharded_clustered_store
from repro.launch.mesh import make_probe_mesh

n, d, k_shard, s = 100_000, 256, 160, 4
xc, _ = clustered_unit_vectors(n, d, n_centers=64, spread=0.25, seed=0,
                               skew=1.3, grouped=True)
mesh = make_probe_mesh(s)
full = SemanticHistogram(jnp.asarray(xc), mesh=mesh)
pred = xc[17]                       # head-concept member (label 0 is first)
ds = np.sort(1.0 - xc @ pred)
builds = {}
for name, kw in (("contiguous", {}),
                 ("balanced", dict(balance="boundary", split_radius=0.35))):
    t0 = time.perf_counter()
    sidx = build_sharded_clustered_store(xc, k_shard, s, iters=6, seed=0,
                                         impl="xla", **kw)
    build_s = time.perf_counter() - t0
    mass = sidx.boundary_mass()
    print(f"ROW|probe_balanced_build|N={n},S={s},zipf1.3,{name}|"
          f"{build_s*1e6:.0f}|mass_spread={mass.max() - mass.min():.0f},"
          f"mass_max={mass.max():.0f}")
    builds[name] = sidx
# one histogram per build, reused across selectivities: the sharded pruned
# probe jits per factory, so rebuilding per sel would re-time compilation
hists = {name: SemanticHistogram(jnp.asarray(xc), mesh=mesh, index=sidx)
         for name, sidx in builds.items()}
for sel in (0.001, 0.01):
    kth = max(1, int(sel * n))
    thr = float(0.5 * (ds[kth - 1] + ds[kth]))
    thr_j = np.asarray([thr], np.float32)
    c_full = full.count_within(pred, thr)
    cf, tf = full.probe_batch(pred[None], thr_j, k=16)
    res = {}
    for name, sidx in builds.items():
        h = hists[name]
        cp, tp = h.probe_batch(pred[None], thr_j, k=16)   # warm + parity
        bitwise = ((np.asarray(cp) == np.asarray(cf)).all()
                   and np.array_equal(np.asarray(tp), np.asarray(tf)))
        sidx.reset_stats()
        c_prn = h.count_within(pred, thr)                 # warm count path
        assert c_prn == c_full, (name, sel, c_prn, c_full)
        st1 = sidx.stats()                                # one-probe stats
        h.count_within(pred, thr)                         # settle caches
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            h.count_within(pred, thr)
        us = (time.perf_counter() - t0) / iters * 1e6
        res[name] = (us, st1["max_shard_rows_scanned"])
        print(f"ROW|probe_balanced_cpu|N={n},S={s},sel={sel:.1%},{name}|"
              f"{us:.0f}|max_shard_rows={st1['max_shard_rows_scanned']},"
              f"spread={st1['spread']:.1%},"
              f"max_frac={st1['max_scan_fraction']:.1%},"
              f"count_diff={c_prn - c_full},topk_bitwise={bitwise}")
    (c_us, c_rows), (b_us, b_rows) = res["contiguous"], res["balanced"]
    print(f"ROW|probe_balanced_cpu|N={n},S={s},sel={sel:.1%},summary|-|"
          f"max_shard_rows {c_rows}->{b_rows} "
          f"({c_rows / max(1, b_rows):.1f}x),time {c_us:.0f}->{b_us:.0f}us "
          f"({c_us / b_us:.1f}x)")
"""


def measure_probe_us(n: int, *, d: int = 1152, k: int = 128,
                     iters: int = 3, seed: int = 0) -> float:
    """Measured wall µs of one jitted single-predicate probe over an (n, d)
    store — the canonical ``probe_measured_cpu`` measurement. Shared with
    ``scripts/check_bench.py``, which re-runs a small subset of these and
    gates on regression vs the persisted ``BENCH_probe_scaling.json``."""
    rng = np.random.default_rng(seed)
    pred = jnp.asarray(rng.standard_normal(d), jnp.float32)
    thr = jnp.asarray([0.5], jnp.float32)
    f = jax.jit(lambda s, p, t: _local_probe(s, p, t, k))
    store = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    f(store, pred, thr)[0].block_until_ready()       # warm the jit
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(store, pred, thr))
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> list[str]:
    rows = [csv_row("bench", "config", "us_per_call", "derived")]
    recs: list[dict] = []

    def add(bench, config, us_per_call, derived) -> None:
        """One row, both as display CSV and as a machine-readable record
        destined for BENCH_probe_scaling.json."""
        rows.append(csv_row(bench, config, us_per_call, derived))
        recs.append({"bench": str(bench), "config": str(config),
                     "us_per_call": str(us_per_call),
                     "derived": str(derived)})

    for n in (10_000, 100_000, 500_000):
        us = measure_probe_us(n)
        add("probe_measured_cpu", f"N={n}", f"{us:.0f}",
            f"{n*1152*4/(us/1e6)/1e9:.1f}GB/s")

    # fresh stream for the remaining sections — they need random data, not
    # any particular draws (all parity checks below are self-consistent)
    rng = np.random.default_rng(0)
    _ = rng.standard_normal(1152)

    # batched multi-predicate probe: one store pass for B predicates.
    # Amortized µs/predicate must collapse vs the B=1 row — that's the PR's
    # claim (store HBM traffic amortized B×, matvec -> MXU matmul).
    n = 100_000
    store = jnp.asarray(rng.standard_normal((n, 1152)), jnp.float32)
    fb = jax.jit(lambda s, p, t: _local_probe_batch(s, p, t, 128))
    base_us = None
    for bsz in (1, 8, 32, 128):
        preds = jnp.asarray(rng.standard_normal((bsz, 1152)), jnp.float32)
        thrs = jnp.full((bsz, 1), 0.5, jnp.float32)
        fb(store, preds, thrs)[0].block_until_ready()
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            jax.block_until_ready(fb(store, preds, thrs))
        us = (time.perf_counter() - t0) / iters * 1e6 / bsz
        if base_us is None:
            base_us = us
        add(
            "probe_batched_cpu", f"N={n},B={bsz}", f"{us:.0f}",
            f"{n*1152*4/(us/1e6)/1e9:.1f}GB/s/pred,speedup={base_us/us:.1f}x")

    # parity: batched == per-predicate scalar loop (same store)
    bsz = 32
    preds = jnp.asarray(rng.standard_normal((bsz, 1152)), jnp.float32)
    thrs = jnp.full((bsz, 1), 0.5, jnp.float32)
    cb, tb = fb(store, preds, thrs)
    max_cnt = 0
    max_top = 0.0
    f1 = jax.jit(lambda s, p, t: _local_probe(s, p, t, 128))
    for j in range(bsz):
        cs, ts = f1(store, preds[j], thrs[j])
        max_cnt = max(max_cnt, int(jnp.abs(cb[j] - cs).max()))
        max_top = max(max_top, float(jnp.abs(tb[j] - ts).max()))
    add("probe_batched_parity", f"N={n},B={bsz}", "-",
        f"count_diff={max_cnt},topk_maxerr={max_top:.2e}")

    # serving layer: coalesced vs sequential per-query probing.
    # Q concurrent queries x F filters: sequential = Q probes of B=F (one
    # per plan_query); coalesced = Q/G probes of B=G*F (micro-batch window
    # merging G queries). Amortized µs/predicate must be monotone
    # non-increasing in G — that's the coalescer's claim.
    q_tot, n_filters = 16, 4
    preds_qf = jnp.asarray(rng.standard_normal((q_tot * n_filters, 1152)),
                           jnp.float32)
    seq_us = None
    for group in (1, 4, 16):
        bsz = group * n_filters
        thrs = jnp.full((bsz, 1), 0.5, jnp.float32)
        probes = [preds_qf[i * bsz:(i + 1) * bsz]
                  for i in range(q_tot // group)]
        fb(store, probes[0], thrs)[0].block_until_ready()
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            for p in probes:
                jax.block_until_ready(fb(store, p, thrs))
        us = (time.perf_counter() - t0) / iters / (q_tot * n_filters) * 1e6
        if seq_us is None:
            seq_us = us
        label = ("sequential" if group == 1 else f"coalesced_g{group}")
        add(
            "probe_coalesced_cpu",
            f"N={n},Q={q_tot},F={n_filters},{label}", f"{us:.0f}",
            f"probes={q_tot // group},speedup={seq_us/us:.1f}x")

    # the real subsystem: PredicateCoalescer end-to-end, Q submitter threads
    # through the micro-batch window (includes lock/window/key overhead the
    # simulated rows above can't see)
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.histogram import SemanticHistogram
    from repro.launch.coalescer import (
        CoalescerConfig,
        PredicateCache,
        PredicateCoalescer,
    )

    hist_co = SemanticHistogram(store)
    q_preds = [np.array(preds_qf[i * n_filters:(i + 1) * n_filters])
               for i in range(q_tot)]
    thr_f = np.full(n_filters, 0.5, np.float32)
    with PredicateCoalescer(
            hist_co,
            CoalescerConfig(max_batch=q_tot * n_filters,
                            window_ms=8.0)) as coal:
        # warm the power-of-two flush buckets so the timed section measures
        # the window/dispatch path, not one-off XLA compiles
        for wb in (4, 8, 16, 32, 64):
            hist_co.probe_batch(np.array(preds_qf[:wb]),
                                np.full(wb, 0.5, np.float32))
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=q_tot) as pool:
            list(pool.map(
                lambda p: coal.selectivity_batch(p, thr_f), q_preds))
        us = (time.perf_counter() - t0) / (q_tot * n_filters) * 1e6
        st = coal.stats()
    add(
        "probe_coalescer_real_cpu",
        f"N={n},Q={q_tot},F={n_filters},window=8ms", f"{us:.0f}",
        f"probes={st['probes_fired']},hit_rate="
        f"{st['cache']['hit_rate']:.0%},speedup={seq_us/us:.1f}x")

    # LRU predicate cache on a hot workload: R requests over U unique
    # predicates (hit rate 1 - U/R); hits skip the store scan entirely.
    uniq, reps = 16, 4
    hot = np.array(preds_qf[:uniq])
    hot /= np.linalg.norm(hot, axis=1, keepdims=True)
    thr_hot = np.full(uniq, 0.5, np.float32)
    for label, cache in (("nocache", None),
                         ("lru1024", PredicateCache(1024))):
        hist = SemanticHistogram(store, cache=cache)
        hist.selectivity_batch(hot, thr_hot)          # warm jit (+ fill)
        t0 = time.perf_counter()
        for _ in range(reps):
            hist.selectivity_batch(hot, thr_hot)
        us = (time.perf_counter() - t0) / (uniq * reps) * 1e6
        hr = (f",hit_rate={cache.stats()['hit_rate']:.0%}" if cache else "")
        add("probe_cached_cpu",
            f"N={n},req={uniq * reps},uniq={uniq},{label}",
            f"{us:.0f}", f"us/request{hr}")

    # cluster-pruned index: scan fraction + speedup vs selectivity on a
    # *clustered* store (image embeddings clump by concept; isotropic
    # gaussians would defeat bound-based pruning). Counts stay exactly equal
    # to the full scan — the pruned rows report how few rows that costs.
    from repro.core.histogram import SemanticHistogram
    from repro.core.synthetic import clustered_unit_vectors
    from repro.index import build_clustered_store

    # K ~ sqrt(N): oversegmentation keeps per-cluster radii tight even when
    # Lloyd's lands in a merged-centers local optimum (docs/index.md)
    n_idx, d_idx, k_idx = 100_000, 256, 256
    xc, _ = clustered_unit_vectors(n_idx, d_idx, n_centers=64, spread=0.25,
                                   seed=0)
    t0 = time.perf_counter()
    cs = build_clustered_store(xc, k_idx, iters=6, seed=0, impl="xla")
    build_s = time.perf_counter() - t0
    add("probe_index_build", f"N={n_idx},K={k_idx}",
        f"{build_s*1e6:.0f}", "kmeans+reorder+radii")
    hist_full = SemanticHistogram(jnp.asarray(xc))
    hist_idx = SemanticHistogram(jnp.asarray(xc), index=cs)
    pred_idx = xc[17]
    d_sorted = np.sort(1.0 - xc @ pred_idx)
    for sel in (0.001, 0.01, 0.1, 0.5):
        kth = max(1, int(sel * n_idx))
        thr = float(0.5 * (d_sorted[kth - 1] + d_sorted[kth]))
        c_full = hist_full.count_within(pred_idx, thr)   # warm + reference
        cs.reset_stats()
        c_prn = hist_idx.count_within(pred_idx, thr)     # warm pruned shapes
        assert c_full == c_prn, (sel, c_full, c_prn)
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            hist_full.count_within(pred_idx, thr)
        full_us = (time.perf_counter() - t0) / iters * 1e6
        t0 = time.perf_counter()
        for _ in range(iters):
            hist_idx.count_within(pred_idx, thr)
        prn_us = (time.perf_counter() - t0) / iters * 1e6
        frac = cs.stats()["scan_fraction"]
        add(
            "probe_pruned_cpu", f"N={n_idx},K={k_idx},sel={sel:.1%}",
            f"{prn_us:.0f}",
            f"scan_frac={frac:.1%},full={full_us:.0f}us,"
            f"speedup={full_us/prn_us:.1f}x,count_diff={c_full-c_prn}")

    # pruned threshold calibration: bound-ordered early-terminated kth
    cs.reset_stats()
    kth_full = hist_full.kth_smallest_distance(pred_idx, 128)
    kth_prn = hist_idx.kth_smallest_distance(pred_idx, 128)
    add(
        "probe_pruned_kth", f"N={n_idx},K={k_idx},k=128", "-",
        f"scan_frac={cs.stats()['scan_fraction']:.1%},"
        f"err={abs(kth_full-kth_prn):.1e}")

    # compound probes (PR 9): one joint-bound pass over a B-way conjunction
    # of correlated predicates (nearest rows of the same planted cluster),
    # each conjunct calibrated to ~1% marginal selectivity. Joint
    # classification prunes at least as hard as the per-predicate union;
    # counts stay bitwise equal to the composed full scan, and check_bench
    # gates that these rows stay within tolerance of the single-predicate
    # probe_pruned_cpu sel=1.0% baseline.
    near = np.argsort(-(xc @ pred_idx))[:4]
    preds_near = xc[near]
    kth_c = max(1, int(0.01 * n_idx))
    thr_near = np.array(
        [np.sort(1.0 - xc @ p)[kth_c - 1] + 1e-6 for p in preds_near])
    for b in (2, 3, 4):
        pb, tb_ = preds_near[:b], thr_near[:b]
        c_cfull = hist_full.count_compound(pb, tb_)    # composed full scan
        cs.reset_stats()
        c_cprn = hist_idx.count_compound(pb, tb_)      # warm pruned shapes
        assert c_cprn == c_cfull, (b, c_cprn, c_cfull)
        frac = cs.stats()["scan_fraction"]
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            hist_full.count_compound(pb, tb_)
        full_us = (time.perf_counter() - t0) / iters * 1e6
        t0 = time.perf_counter()
        for _ in range(iters):
            hist_idx.count_compound(pb, tb_)
        prn_us = (time.perf_counter() - t0) / iters * 1e6
        add("probe_compound_cpu", f"N={n_idx},K={k_idx},B={b},sel=1.0%",
            f"{prn_us:.0f}",
            f"scan_frac={frac:.1%},full={full_us:.0f}us,"
            f"speedup={full_us/prn_us:.1f}x,count_diff={c_cprn - c_cfull}")

    # mutable store (PR 7): (a) incremental vs full index rebuild after 10%
    # drift — the k-means warm start + batched re-split + shard-sticky
    # repack must make catching up with drift >= 3x cheaper than building
    # from scratch (check_bench gates these rows); (b) the hot-tail scan
    # overhead probes pay between rebuilds, vs tail fraction.
    from repro.index import MutableClusteredStore

    n_new = int(0.10 * n_idx)
    drift_rows = xc[rng.permutation(n_idx)[:n_new]] \
        + 0.05 * rng.standard_normal((n_new, d_idx)).astype(np.float32)
    drift_rows /= np.linalg.norm(drift_rows, axis=1, keepdims=True)
    rebuild_s = {}
    for mode in ("full", "incremental"):
        ms = MutableClusteredStore(xc, k_idx, impl="xla", iters=6, seed=0,
                                   auto_rebuild=False,
                                   incremental=(mode == "incremental"))
        ms.insert(drift_rows.astype(np.float32))
        ms.delete(list(range(n_new)))            # 10% churn both ways
        t0 = time.perf_counter()
        assert ms.rebuild(wait=True)
        rebuild_s[mode] = time.perf_counter() - t0
        st_m = ms.stats()
        add("probe_mutable_rebuild",
            f"N={n_idx},K={k_idx},drift=10%,{mode}",
            f"{rebuild_s[mode]*1e6:.0f}",
            f"incremental={st_m['last_rebuild_incremental']},"
            f"tail_after={st_m['tail_rows']},dead_after="
            f"{st_m['base_dead']}")
    add("probe_mutable_rebuild", f"N={n_idx},K={k_idx},drift=10%,summary",
        "-", f"full {rebuild_s['full']*1e6:.0f}us -> incremental "
        f"{rebuild_s['incremental']*1e6:.0f}us "
        f"({rebuild_s['full']/rebuild_s['incremental']:.1f}x cheaper)")

    # hot-tail overhead: counts stay exact at every tail size; the rows
    # show what the unindexed full-scan tail costs a 1%-selectivity probe
    ms = MutableClusteredStore(xc, k_idx, impl="xla", iters=6, seed=0,
                               auto_rebuild=False)
    hist_mut = SemanticHistogram(jnp.asarray(xc), index=ms)
    kth = max(1, int(0.01 * n_idx))
    thr_mut = float(0.5 * (d_sorted[kth - 1] + d_sorted[kth]))
    base_mut_us = None
    grown = 0
    tail_all = np.zeros((0, d_idx), np.float32)
    for tail_frac in (0.0, 0.05, 0.25):
        target = int(tail_frac * n_idx)
        if target > grown:
            extra = np.ascontiguousarray(
                xc[rng.permutation(n_idx)[:target - grown]])
            ms.insert(extra)
            tail_all = np.concatenate([tail_all, extra])
            grown = target
        # exactness oracle: an index-free full scan over base + tail rows
        oracle = SemanticHistogram(
            jnp.asarray(np.concatenate([xc, tail_all])))
        c_ref = oracle.count_within(pred_idx, thr_mut)
        c_mut = hist_mut.count_within(pred_idx, thr_mut)   # warm shapes
        assert c_mut == c_ref, (tail_frac, c_mut, c_ref)
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            hist_mut.count_within(pred_idx, thr_mut)
        us = (time.perf_counter() - t0) / iters * 1e6
        if base_mut_us is None:
            base_mut_us = us
        add("probe_mutable_tail_cpu",
            f"N={n_idx},K={k_idx},sel=1.0%,tail={tail_frac:.0%}",
            f"{us:.0f}",
            f"overhead={us/base_mut_us:.2f}x_vs_empty_tail,"
            f"count_diff={c_mut - c_ref}")

    # per-shard pruned probes on a host-local mesh: the PR-4 composition.
    # Forcing host devices must happen before jax initializes, so this
    # section runs in a subprocess (same trick as repro.launch.dryrun);
    # the child prints ROW|-delimited fields the parent re-emits as CSV.
    # Acceptance: sharded-pruned scan fraction < 10% at <= 1% selectivity
    # on a clustered 100k store over >= 4 host-local shards.
    import os
    import subprocess

    child = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             # without this, jax's accelerator-plugin probe can stall the
             # child for minutes (see tests/conftest.py)
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(_ROOT / "src")})
    if child.returncode:
        add("probe_sharded_pruned_cpu", "S=4", "-",
            f"FAILED:{child.stderr.strip()[-200:]}")
    else:
        for line in child.stdout.splitlines():
            if line.startswith("ROW|"):
                add(*line.split("|")[1:])

    # boundary-mass-balanced vs contiguous index build on a Zipf-skewed
    # grouped store (PR 5) — same forced-host-devices subprocess trick
    child = subprocess.run(
        [sys.executable, "-c", _BALANCED_CHILD],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(_ROOT / "src")})
    if child.returncode:
        add("probe_balanced_cpu", "S=4", "-",
            f"FAILED:{child.stderr.strip()[-200:]}")
    else:
        for line in child.stdout.splitlines():
            if line.startswith("ROW|"):
                add(*line.split("|")[1:])

    # v5e analytic: per-chip probe time for a pod-scale store
    for total in (1e8, 1e9):
        per_chip = total / 256
        bytes_chip = per_chip * 1152 * 4
        t_mem = bytes_chip / HBM_BW
        t_coll = (128 * 4 * 2) / LINK_BW  # all-gather top-k + psum counts
        add(
            "probe_v5e_analytic", f"N={total:.0e},256chips",
            f"{(t_mem + t_coll)*1e6:.0f}",
            f"mem={t_mem*1e6:.0f}us,coll={t_coll*1e6:.2f}us")
    add("probe_v5e_analytic", "conclusion", "-",
        "collective O(k) -> probe scales linearly in N/chips")

    # persist the run machine-readably at the repo root: rows + the store
    # configs the headline rows used + the git sha, so per-PR trajectories
    # (scan fractions, max-shard rows, speedups) are diffable across PRs
    import json

    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_ROOT,
                             capture_output=True, text=True,
                             timeout=30).stdout.strip() or None
    except OSError:
        sha = None
    (_ROOT / "BENCH_probe_scaling.json").write_text(json.dumps({
        "bench": "bench_probe_scaling",
        "git_sha": sha,
        "config": {
            "single_device": {"dims": 1152, "store_rows": [10_000, 100_000,
                                                           500_000]},
            "pruned_index": {"n": 100_000, "dims": 256, "k_clusters": 256},
            "compound": {"n": 100_000, "dims": 256, "k_clusters": 256,
                         "widths": [2, 3, 4], "marginal_sel": 0.01},
            "sharded": {"n": 100_000, "dims": 256, "shards": 4,
                        "k_per_shard": 160},
            "balanced": {"n": 100_000, "dims": 256, "shards": 4,
                         "k_per_shard": 160, "zipf_skew": 1.3,
                         "grouped": True, "split_radius": 0.35},
            "mutable": {"n": 100_000, "dims": 256, "k_clusters": 256,
                        "drift": 0.10, "tail_fracs": [0.0, 0.05, 0.25]},
        },
        "rows": recs,
    }, indent=1) + "\n")
    return rows


if __name__ == "__main__":
    for row in main():
        print(row)
