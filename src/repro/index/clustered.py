"""Cluster-pruned probe index: sublinear *exact* selectivity (paper §2 + §3.2).

Every probe so far streamed the full (N, d) store, even when the implicit
range query — cosine distance to the predicate under a threshold — matches a
handful of images. A semantic filter is a range query on the embedding
sphere, so an IVF-style centroid partition gives *exact* per-cluster count
bounds and lets a probe skip almost all of a low-selectivity store:

  partition   k-means (``repro.kernels.kmeans``) splits the store into K
              clusters; the store is **reordered cluster-contiguous** so a
              cluster is one slice, with ``offsets`` (K+1,), the centroids,
              and per-cluster radii ``r_c = max ||x - mu_c||``.

  bounds      for predicate p, the kernel's distance is 1 - p.x. Writing
              x = mu_c + (x - mu_c) and applying Cauchy-Schwarz:

                  dist(p, x) in [d_c - ||p|| r_c,  d_c + ||p|| r_c],
                  d_c = 1 - p.mu_c

              For unit p on the unit sphere this is exactly the triangle
              inequality on Euclidean caps (||p-x||^2 = 2 dist); the inner-
              product form stays exact for *any* p and needs no sqrt.

  classify    against threshold tau, each cluster is
                all-in    ub_c <= tau - eps   count += size_c, scan nothing
                all-out   lb_c >  tau + eps   skip
                boundary  otherwise           scan (the only rows touched)
              eps (default 1e-4) absorbs the gap between the f64 bound
              arithmetic here and the kernel's f32 distances, so pruned
              counts are **exactly** the full-scan counts — never estimates.

  scan        boundary segments are gathered into one buffer, padded to a
              power-of-two bucket, and scored by ONE
              ``cosine_topk.cosine_probe_batch_masked`` launch (the valid
              prefix length is a runtime SMEM scalar, so the kernel compiles
              per bucket shape, not per subset). The batched probe takes the
              union of boundary clusters across all B predicates — still one
              launch per probe call.

Top-k stays exact too: ``probe_pruned`` over-covers with every cluster whose
lower bound could reach the k-th smallest distance (tau_k = the k-th
smallest of the size-weighted upper bounds), and ``kth_smallest`` scans
clusters in ascending-lower-bound order, terminating as soon as the current
k-th candidate is provably below every unscanned cluster — the paper's
threshold-calibration probe (§3.2) without the full pass.

Scan-fraction accounting: every launch records rows scanned vs the N rows a
full scan would stream; ``stats()`` exposes the cumulative fraction for the
serve driver and ``bench_probe_scaling``.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cosine_topk.ref import (
    HIGHEST,
    cosine_probe_batch_masked_ref,
)
from repro.kernels.kmeans.ops import kmeans
from repro.obs import spans

f32 = jnp.float32

__all__ = ["ClusteredStore", "ScanPlan", "build_clustered_store",
           "gather_rows", "store_from_fragments"]


@jax.jit
def gather_rows(store, rows):
    """The scan buffer ``store[rows]``, as a device program of its own.

    Jitted at module level so the gather carries one name on the device
    trace (``jit_gather_rows``). It stays apart from the masked scan:
    fused into one program, XLA folds the gather into the distance
    contraction and per-row distances drift from the full scan's
    (``make_sharded_pruned_probe``'s docstring in core/histogram.py).
    """
    return jnp.take(store, rows, axis=0)


@partial(jax.jit, static_argnames=("k",))
def _masked_probe_batch_xla(store, n_valid, preds, thr, *, k: int):
    """XLA twin of ``cosine_probe_batch_masked`` — the jitted ref oracle.

    Per-row distances are bitwise the rows' full-scan distances (the
    einsum's dot reduction is row-local), so pruned counts match the full
    batched scan exactly.
    """
    return cosine_probe_batch_masked_ref(store, n_valid, preds, thr, k)


@partial(jax.jit, static_argnames=("mode",))
def _compound_masked_xla(store, n_valid, preds, thr, *, mode: str):
    """One masked launch scoring a whole conjunction/disjunction.

    Per-row distances come from the same ``nd,bd->bn`` contraction as every
    batched scan twin, so each conjunct's per-row match decision is bitwise
    the decision a full batched scan makes for that row — the compound
    count is then exactly the AND/OR of the full scans' row sets. Dead
    (padding) rows score +inf for every conjunct, so they match nothing
    under either mode.
    """
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(jnp.arange(store.shape[0])[None, :] < n_valid,
                      1.0 - sims, jnp.inf)
    match = dists <= thr[:, None]                       # (B, n)
    hit = match.all(axis=0) if mode == "and" else match.any(axis=0)
    return hit.sum().astype(jnp.int32)


@partial(jax.jit, static_argnames=("k",))
def _masked_probe_xla(store, n_valid, pred, thr, *, k: int):
    """Scalar twin mirroring ``histogram._local_probe``'s ``nd,d->n``
    einsum, so a pruned one-predicate scan is bitwise the full scalar scan.
    Deliberately NOT the batched ref at B=1: the scalar and batched einsum
    contractions may reduce in different orders on some XLA backends."""
    sims = jnp.einsum("nd,d->n", store.astype(f32), pred.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(jnp.arange(store.shape[0]) < n_valid,
                      1.0 - sims, jnp.inf)
    counts = (dists[None, :] <= thr[:, None]).sum(axis=1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts.astype(jnp.int32), -neg_top


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """Host-side classification of one (batched) probe against the clusters.

    The plan is what survives the exact bound arithmetic: which clusters the
    kernel must actually scan (``scan_ids`` — boundary clusters, plus the
    top-k cover when the caller needs top-k), how many rows that is (``m``),
    and the counts already *resolved* by bounds alone (``extra`` — all-in
    sizes of clusters outside the scan union). It deliberately carries no
    device buffers, so the sharded probe can plan every shard on the host
    and launch one shard_map over the per-shard gathered segments.
    """

    scan_ids: np.ndarray        # cluster ids the kernel must scan (union)
    m: int                      # rows those clusters hold
    extra: np.ndarray           # (B, T) int64 — bound-resolved counts
    boundary_clusters: int      # boundary classifications across the batch


@dataclasses.dataclass
class ClusteredStore:
    """K-cluster partition of an embedding store with exact probe pruning.

    Attach to a ``SemanticHistogram(index=...)`` to route its probes through
    the pruned path; or call ``probe_pruned`` / ``kth_smallest`` directly.
    ``embeddings`` is the *reordered* (cluster-contiguous) store; ``perm``
    maps reordered row -> original row id. Counts and top-k distances are
    permutation-invariant, so results are interchangeable with a full scan
    of the original store.
    """

    embeddings: jax.Array      # (N, d) f32, cluster-contiguous
    offsets: np.ndarray        # (K+1,) int64 segment boundaries
    sizes: np.ndarray          # (K,) int64 cluster sizes
    centroids: np.ndarray      # (K, d) float64
    radii: np.ndarray          # (K,) float64, max ||x - mu_c|| per cluster
    perm: np.ndarray           # (N,) original row ids in cluster order
    eps: float = 1e-4          # bound slack covering f32-vs-f64 roundoff
    chunk_rows: int = 4096     # kth_smallest: min rows per incremental scan
    max_row_norm: float = 1.0  # max ||x|| over the store (global dist floor)

    def __post_init__(self):
        self.n = int(self.embeddings.shape[0])
        self.k_clusters = int(self.sizes.shape[0])
        self._lock = threading.Lock()
        # rows_gathered: rows the kernel reads, power-of-two padding
        # included (rows_scanned counts the valid ones)
        self._cum = {"probes": 0, "launches": 0, "rows_scanned": 0,
                     "rows_full_equiv": 0, "rows_gathered": 0}
        # telemetry hub (repro.obs.ObsHub), attached by the serve layer;
        # duck-typed so the index never imports the hub
        self.obs = None

    # ------------------------------------------------------------- bounds

    def cluster_bounds(self, preds: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Exact per-cluster distance bounds (lb, ub), each (B, K) float64.

        dist(p, x) = 1 - p.x in [d_c - ||p|| r_c, d_c + ||p|| r_c] for every
        x in cluster c (Cauchy-Schwarz on x - mu_c); f64 so eps covers the
        kernel's f32 rounding with orders of magnitude to spare.
        """
        p64 = np.asarray(preds, np.float64)
        d_mu = 1.0 - p64 @ self.centroids.T                 # (B, K)
        pnorm = np.linalg.norm(p64, axis=1, keepdims=True)
        rad = pnorm * self.radii[None, :]
        # global floor: dist = 1 - p.x >= 1 - ||p|| max||x|| for every row,
        # so a cluster whose centroid the predicate sits inside (d_c < r_c)
        # still all-outs thresholds below the reachable minimum
        return np.maximum(d_mu - rad, 1.0 - pnorm * self.max_row_norm), \
            d_mu + rad

    def live_cluster_sizes(self, live: np.ndarray) -> np.ndarray:
        """(K,) int64 live-row count per cluster for a (N,) bool mask over
        the *stored* (cluster-contiguous) row order. The mutable store
        maintains this incrementally; this helper recomputes from scratch
        for callers that only have the mask."""
        cl = np.repeat(np.arange(self.k_clusters), self.sizes)
        return np.bincount(cl[np.asarray(live, bool)],
                           minlength=self.k_clusters).astype(np.int64)

    def count_bounds(self, preds: np.ndarray, thresholds: np.ndarray, *,
                     live_sizes: np.ndarray | None = None,
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Exact count interval per (predicate, threshold) — zero rows read.

        preds (B, d); thresholds (B,) or (B, T). Returns (lo, hi), each
        (B, T) int64: lo sums all-in cluster sizes, hi sums every cluster
        that is not all-out. The same eps-slacked f64 bound arithmetic that
        makes pruned scans bitwise-exact guarantees lo <= true count <= hi,
        so the serving layer can answer from bounds alone (degraded mode)
        with a certified interval when the scan path is unavailable.

        ``live_sizes`` (K,) substitutes per-cluster live-row counts for the
        built sizes under tombstones: every live row is still a member of
        its build-time cluster, so the distance bounds hold for the live
        subset and the interval stays certified.
        """
        preds = np.asarray(preds, np.float32)       # match the probe path
        thr64 = np.asarray(thresholds, np.float64)
        if thr64.ndim == 1:
            thr64 = thr64[:, None]
        lb, ub = self.cluster_bounds(preds)                      # (B, K)
        allin = ub[:, :, None] <= thr64[:, None, :] - self.eps   # (B, K, T)
        allout = lb[:, :, None] > thr64[:, None, :] + self.eps
        sz = self.sizes if live_sizes is None else \
            np.asarray(live_sizes, np.int64)
        sizes = sz[None, :, None]
        lo = (allin.astype(np.int64) * sizes).sum(axis=1)
        hi = ((~allout).astype(np.int64) * sizes).sum(axis=1)
        return lo, hi

    def _topk_cover(self, lb: np.ndarray, ub: np.ndarray, k: int,
                    sizes: np.ndarray | None = None) -> np.ndarray:
        """(B, K) mask of clusters that could hold a top-k distance.

        tau_k — the k-th smallest of the size-weighted upper bounds — is an
        upper bound on the true k-th smallest distance, so every cluster
        with lb <= tau_k + eps must be scanned and no other cluster can
        contribute to the top-k. ``sizes`` substitutes live counts under
        tombstones (each cluster still holds >= that many live rows below
        its ub, so tau_k stays an upper bound on the live k-th distance).
        """
        if sizes is None:
            sizes = self.sizes
        nonempty = sizes > 0
        ne_ids = np.flatnonzero(nonempty)
        cover = np.zeros(lb.shape, bool)
        if not len(ne_ids):
            return cover
        for b in range(lb.shape[0]):
            order = ne_ids[np.argsort(ub[b, ne_ids], kind="stable")]
            csum = np.cumsum(sizes[order])
            pos = min(int(np.searchsorted(csum, k)), len(order) - 1)
            tau_k = ub[b, order[pos]]
            cover[b] = nonempty & (lb[b] <= tau_k + self.eps)
        return cover

    # ------------------------------------------------------------ planning

    def plan_scan(self, preds: np.ndarray, thr: np.ndarray, *, k: int = 1,
                  need_topk: bool = True,
                  live_sizes: np.ndarray | None = None) -> ScanPlan:
        """Classify every cluster for a batched probe; return the ScanPlan.

        preds (B, d); thr (B, T). All-in / all-out clusters resolve to
        ``extra`` counts without touching a row; the scan union is the
        boundary clusters across the batch (plus the top-k cover when
        ``need_topk``). A near-total union (>= 90% of rows) is promoted to
        the whole store so the gather below degenerates to the contiguous
        embeddings — the kernel then counts every cluster row-by-row, which
        is still exact, and the worst case costs ~the full scan and no more.

        ``live_sizes`` (K,) — per-cluster live-row counts under the mutable
        store's tombstones. Every live row is a build-time member of its
        cluster, so the distance bounds stay valid for the live subset;
        all-in clusters then contribute their *live* count, ``m`` counts
        live rows only, and the full-store promotion compares against the
        live total (dead rows are never gathered, see ``scan_rows``).
        """
        with spans.span(spans.INDEX_PLAN_SCAN):
            sizes = self.sizes if live_sizes is None else \
                np.asarray(live_sizes, np.int64)
            n_live = int(sizes.sum())
            lb, ub = self.cluster_bounds(preds)                  # (B, K) f64
            thr64 = np.asarray(thr, np.float64)
            # (B, K, T)
            allin = ub[:, :, None] <= thr64[:, None, :] - self.eps
            allout = lb[:, :, None] > thr64[:, None, :] + self.eps
            nonempty = sizes > 0
            boundary = (~(allin | allout)).any(axis=2) & nonempty[None, :]
            scan_bk = boundary.copy()                            # (B, K)
            if need_topk:
                scan_bk |= self._topk_cover(
                    lb, ub, max(1, min(int(k), max(n_live, 1))), sizes)
            in_union = scan_bk.any(axis=0) & nonempty            # (K,)
            scan_ids = np.flatnonzero(in_union)
            if int(sizes[scan_ids].sum()) >= 0.9 * n_live:
                in_union = nonempty.copy()
                scan_ids = np.flatnonzero(in_union)
            # clusters resolved by bounds alone: add all-in sizes. The
            # scan buffer is scored against *every* predicate, so any
            # cluster in the union — even one this predicate classified
            # all-in — is counted row-by-row by the kernel, exactly; only
            # clusters outside the union contribute via their bound
            # classification.
            resolved = nonempty[None, :] & ~in_union[None, :]    # (B, K)
            extra = ((allin & resolved[:, :, None]).astype(np.int64)
                     * sizes[None, :, None]).sum(axis=1)         # (B, T)
            return ScanPlan(scan_ids=scan_ids,
                            m=int(sizes[scan_ids].sum()), extra=extra,
                            boundary_clusters=int(boundary.sum()))

    def scan_rows(self, cluster_ids: np.ndarray,
                  live: np.ndarray | None = None) -> np.ndarray:
        """Local row indices of the given clusters' segments, concatenated
        in cluster order (the layout is cluster-contiguous). ``live`` (N,)
        bool drops tombstoned rows — the scan buffer then holds live rows
        only, so pruned results match a fresh store built from the live
        subset bitwise (per-row distances are row-local)."""
        if not len(cluster_ids):
            return np.empty(0, np.int64)
        rows = np.concatenate(
            [np.arange(self.offsets[c], self.offsets[c + 1])
             for c in cluster_ids])
        if live is not None:
            rows = rows[np.asarray(live, bool)[rows]]
        return rows

    # -------------------------------------------------------------- scans

    def _gather(self, cluster_ids: np.ndarray,
                live: np.ndarray | None = None,
                live_sizes: np.ndarray | None = None,
                ) -> tuple[jax.Array, int]:
        """Concatenate cluster segments, pad to a power-of-two bucket.

        Returns (buffer (bucket, d), valid row count). Padding repeats row 0
        and is masked to +inf distance by the kernel, so it never scores.
        When every row is selected (high-selectivity probes prune nothing)
        the store is already the contiguous answer — no gather copy; under
        tombstones (``live``) the zero-copy shortcut is disabled because
        dead rows must never enter the scan.
        """
        with spans.span(spans.INDEX_GATHER):
            if live is None:
                m = int(self.sizes[cluster_ids].sum())
                if m == self.n:
                    return self.embeddings, m
                rows = self.scan_rows(cluster_ids)
            else:
                sizes = self.live_cluster_sizes(live) \
                    if live_sizes is None else live_sizes
                m = int(np.asarray(sizes)[cluster_ids].sum())
                rows = self.scan_rows(cluster_ids, live)
            bucket = max(128, 1 << max(0, m - 1).bit_length())
            pad = np.zeros(bucket - m, np.int64)
            buf = gather_rows(self.embeddings,
                              jnp.asarray(np.concatenate([rows, pad])))
            return buf, m

    def _masked_probe(self, buf, m, preds, thr, *, k, impl, interpret,
                      scalar):
        """Dispatch a masked subset scan through the same kernel *shape* as
        the full-scan path it replaces: each impl's scalar and batch kernels
        reduce the dot product in different orders (VPU reduce vs MXU
        matmul), so a pruned scalar probe must use the scalar kernel and a
        pruned batched probe the batch kernel — even at B=1, where
        ``probe_batch`` without an index still runs the batch kernel —
        to keep pruned results bitwise equal to the full scan.
        """
        with spans.span(spans.INDEX_SCAN):
            nv = jnp.asarray(m, jnp.int32)
            if impl == "pallas":
                from repro.kernels.cosine_topk import ops as ct

                if scalar:
                    counts, topk = ct.cosine_probe_masked(
                        buf, nv, preds[0], thr[0], k=k, interpret=interpret)
                    return counts[None], topk[None]
                return ct.cosine_probe_batch_masked(buf, nv, preds, thr, k=k,
                                                    interpret=interpret)
            if scalar:
                counts, topk = _masked_probe_xla(buf, nv, preds[0], thr[0],
                                                 k=k)
                return counts[None], topk[None]
            return _masked_probe_batch_xla(buf, nv, preds, thr, k=k)

    # -------------------------------------------------------------- probe

    def probe_pruned(self, preds: np.ndarray, thresholds: np.ndarray, *,
                     k: int = 1, impl: str = "xla",
                     interpret: bool | None = None,
                     scalar_kernel: bool = False, need_topk: bool = True,
                     live: np.ndarray | None = None,
                     live_sizes: np.ndarray | None = None,
                     ) -> tuple[np.ndarray, np.ndarray, dict]:
        """Pruned batched probe: counts + top-k exactly equal the full scan.

        preds (B, d); thresholds (B,) or (B, T). Classifies every cluster
        against every (predicate, threshold); all-in clusters contribute
        their size with zero rows touched, all-out contribute nothing, and
        the union of boundary (+ top-k cover) segments across the batch is
        scored by at most ONE masked kernel launch. Returns
        (counts (B, T) int32, top-k (B, k) float32, per-call stats).

        ``scalar_kernel``: scan with the scalar-probe kernel shape (the
        histogram's non-batched entry points) instead of the batch kernel —
        bitwise parity requires matching the full-scan path's kernel.
        ``need_topk=False`` (count-only callers that discard the top-k)
        skips the top-k cover: a probe whose every cluster resolves by
        bounds then launches nothing, and the returned top-k is +inf.

        ``live``/``live_sizes``: tombstone support for the mutable store —
        dead rows are excluded from every gather, all-in clusters
        contribute live counts, and results equal a fresh full scan of the
        live subset bitwise. The indexed rows' bounds stay valid because
        live rows are a subset of each cluster's build-time members.
        """
        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32)
        if thr.ndim == 1:
            thr = thr[:, None]
        b, t = thr.shape
        if live is not None and live_sizes is None:
            live_sizes = self.live_cluster_sizes(live)
        n_eff = self.n if live_sizes is None \
            else int(np.asarray(live_sizes).sum())
        k = max(1, min(int(k), max(n_eff, 1)))
        plan = self.plan_scan(preds, thr, k=k, need_topk=need_topk,
                              live_sizes=live_sizes)

        if len(plan.scan_ids) and plan.m:
            buf, m = self._gather(plan.scan_ids, live, live_sizes)
            gathered = int(buf.shape[0])
            counts_s, topk = self._masked_probe(
                buf, m, jnp.asarray(preds), jnp.asarray(thr), k=k,
                impl=impl, interpret=interpret, scalar=scalar_kernel)
        else:                       # every cluster resolved by its bounds
            m = gathered = 0
            counts_s = np.zeros((b, t), np.int32)
            topk = jnp.full((b, k), jnp.inf, f32)

        counts = (np.asarray(counts_s, np.int64) + plan.extra
                  ).astype(np.int32)

        stats = {
            "launches": 1 if m else 0,
            "rows_scanned": m,
            "rows_gathered": gathered,
            "rows_full_equiv": n_eff,
            "scan_fraction": m / max(1, n_eff),
            "scanned_clusters": int(len(plan.scan_ids)),
            "boundary_clusters": plan.boundary_clusters,
            "clusters": self.k_clusters,
            "batch": b,
        }
        self._record(stats, probes=1)
        return counts, np.asarray(topk), stats

    # ----------------------------------------------------------- compound

    @staticmethod
    def _compound_classes(allin_pk: np.ndarray, allout_pk: np.ndarray,
                          mode: str) -> tuple[np.ndarray, np.ndarray]:
        """Joint (K,) all-in / all-out masks from per-predicate (B, K) ones.

        AND: a cluster is all-out the moment ANY conjunct all-outs it, and
        all-in only when EVERY conjunct all-ins it. OR is the De Morgan
        dual. This is why conjunctions prune *harder* than per-predicate
        probes: the joint all-out set is the union of the per-predicate
        all-out sets, so the surviving boundary set is a subset of every
        per-predicate boundary union.
        """
        if mode == "and":
            return allin_pk.all(axis=0), allout_pk.any(axis=0)
        return allin_pk.any(axis=0), allout_pk.all(axis=0)

    def plan_compound(self, preds: np.ndarray, thr: np.ndarray, *,
                      mode: str = "and",
                      live_sizes: np.ndarray | None = None) -> ScanPlan:
        """Classify every cluster against a whole conjunction/disjunction.

        preds (B, d) are the B conjuncts of ONE compound predicate; thr (B,)
        their per-conjunct thresholds. Unlike ``plan_scan`` — which unions
        boundary sets across independent predicates — the per-conjunct
        all-in/all-out sets are intersected *before* any boundary scan, so
        the scan union only holds clusters the compound itself cannot
        resolve. ``extra`` is (1, 1): the summed size of bound-resolved
        all-in clusters (rows matching every conjunct / at least one,
        by mode) outside the scan union.
        """
        if mode not in ("and", "or"):
            raise ValueError(f"mode must be 'and' or 'or', got {mode!r}")
        sizes = self.sizes if live_sizes is None else \
            np.asarray(live_sizes, np.int64)
        n_live = int(sizes.sum())
        lb, ub = self.cluster_bounds(preds)                  # (B, K) f64
        thr64 = np.asarray(thr, np.float64).reshape(-1, 1)   # (B, 1)
        allin_pk = ub <= thr64 - self.eps                    # (B, K)
        allout_pk = lb > thr64 + self.eps
        allin, allout = self._compound_classes(allin_pk, allout_pk, mode)
        nonempty = sizes > 0
        boundary = ~(allin | allout) & nonempty              # (K,)
        in_union = boundary.copy()
        scan_ids = np.flatnonzero(in_union)
        if int(sizes[scan_ids].sum()) >= 0.9 * n_live:
            in_union = nonempty.copy()
            scan_ids = np.flatnonzero(in_union)
        resolved = nonempty & ~in_union
        extra = np.array([[int(sizes[allin & resolved].sum())]], np.int64)
        return ScanPlan(scan_ids=scan_ids,
                        m=int(sizes[scan_ids].sum()), extra=extra,
                        boundary_clusters=int(boundary.sum()))

    def compound_count_bounds(self, preds: np.ndarray,
                              thresholds: np.ndarray, *, mode: str = "and",
                              live_sizes: np.ndarray | None = None,
                              ) -> tuple[int, int]:
        """Certified (lo, hi) interval on the compound match count — zero
        rows read. lo sums joint all-in cluster sizes, hi sums every
        cluster not jointly all-out; the joint classes come from the same
        eps-slacked f64 bounds as ``count_bounds``, so
        lo <= true compound count <= hi."""
        if mode not in ("and", "or"):
            raise ValueError(f"mode must be 'and' or 'or', got {mode!r}")
        preds = np.asarray(preds, np.float32)
        lb, ub = self.cluster_bounds(preds)
        thr64 = np.asarray(thresholds, np.float64).reshape(-1, 1)
        allin, allout = self._compound_classes(
            ub <= thr64 - self.eps, lb > thr64 + self.eps, mode)
        sizes = self.sizes if live_sizes is None else \
            np.asarray(live_sizes, np.int64)
        return int(sizes[allin].sum()), int(sizes[~allout & (sizes > 0)].sum())

    def probe_compound(self, preds: np.ndarray, thresholds: np.ndarray, *,
                       mode: str = "and", live: np.ndarray | None = None,
                       live_sizes: np.ndarray | None = None,
                       ) -> tuple[int, dict]:
        """Exact compound match count in ONE masked launch over the joint
        boundary union. Bitwise-equal to composing full batched XLA scans:
        the launch scores every surviving row against every conjunct with
        the same ``nd,bd->bn`` contraction the full scan uses (per-row
        reductions are row-local, so gathering a subset never changes a
        row's distance), then ANDs/ORs the per-row match bits.

        The gather always pads to an explicit power-of-two bucket — never
        the zero-copy full-store shortcut — so no real row lands in a
        trailing remainder loop and per-row scores match the row-stable
        full-scan reference exactly. Returns (count, stats) with the same
        stats keys as ``probe_pruned``.
        """
        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32).reshape(-1)
        if preds.ndim != 2 or preds.shape[0] != thr.shape[0]:
            raise ValueError(
                f"preds {preds.shape} and thresholds {thr.shape} must agree "
                f"on the number of conjuncts")
        if live is not None and live_sizes is None:
            live_sizes = self.live_cluster_sizes(live)
        n_eff = self.n if live_sizes is None \
            else int(np.asarray(live_sizes).sum())
        plan = self.plan_compound(preds, thr, mode=mode,
                                  live_sizes=live_sizes)

        if len(plan.scan_ids) and plan.m:
            rows = self.scan_rows(plan.scan_ids, live)
            m = int(len(rows))
            bucket = max(128, 1 << max(0, m - 1).bit_length())
            pad = np.zeros(bucket - m, np.int64)
            buf = gather_rows(self.embeddings,
                              jnp.asarray(np.concatenate([rows, pad])))
            scanned = int(_compound_masked_xla(
                buf, jnp.asarray(m, jnp.int32), jnp.asarray(preds),
                jnp.asarray(thr), mode=mode))
        else:
            m = bucket = 0
            scanned = 0
        count = scanned + int(plan.extra[0, 0])

        stats = {
            "launches": 1 if m else 0,
            "rows_scanned": m,
            "rows_gathered": bucket,
            "rows_full_equiv": n_eff,
            "scan_fraction": m / max(1, n_eff),
            "scanned_clusters": int(len(plan.scan_ids)),
            "boundary_clusters": plan.boundary_clusters,
            "clusters": self.k_clusters,
            "batch": int(preds.shape[0]),
        }
        self._record(stats, probes=1)
        return count, stats

    def kth_smallest(self, pred: np.ndarray, k: int, *, impl: str = "xla",
                     interpret: bool | None = None,
                     live: np.ndarray | None = None,
                     live_sizes: np.ndarray | None = None) -> float:
        """Exact k-th smallest distance via bound-ordered cluster scanning.

        Clusters are visited in ascending lower-bound order, ``chunk_rows``
        rows at a time; the loop stops as soon as the running k-th candidate
        is <= the next cluster's lower bound - eps (no unscanned point can
        beat it). Equals the full-scan value bit for bit — the threshold-
        calibration primitive (§3.2) without the full pass. ``live`` drops
        tombstoned rows (bounds stay valid for any member subset), matching
        a fresh full scan of the live rows.
        """
        pred = np.asarray(pred, np.float32)
        if live is not None and live_sizes is None:
            live_sizes = self.live_cluster_sizes(live)
        sizes = self.sizes if live_sizes is None \
            else np.asarray(live_sizes, np.int64)
        n_eff = int(sizes.sum())
        k = max(1, min(int(k), max(n_eff, 1)))
        lb, _ = self.cluster_bounds(pred[None])
        lb = lb[0]
        ne = np.flatnonzero(sizes > 0)
        order = ne[np.argsort(lb[ne], kind="stable")]
        preds_j = jnp.asarray(pred)[None, :]
        thr_j = jnp.zeros((1, 1), f32)
        best = np.empty(0, np.float32)
        i, launches, rows_scanned, rows_gathered = 0, 0, 0, 0
        # chunk target: enough rows per launch to amortize dispatch without
        # defeating early termination on small stores
        target = max(k, min(self.chunk_rows, max(1, n_eff // 8)))
        while i < len(order):
            if best.size >= k and best[k - 1] <= lb[order[i]] - self.eps:
                break
            j, nrows = i, 0
            while j < len(order) and (j == i or nrows < target):
                nrows += int(sizes[order[j]])
                j += 1
            buf, m = self._gather(order[i:j], live, sizes)
            _, topk = self._masked_probe(buf, m, preds_j, thr_j,
                                         k=min(k, m), impl=impl,
                                         interpret=interpret, scalar=True)
            got = np.asarray(topk[0])
            best = np.sort(np.concatenate([best, got[np.isfinite(got)]]),
                           kind="stable")[:k]
            launches += 1
            rows_scanned += m
            rows_gathered += int(buf.shape[0])
            i = j
        self._record({"launches": launches, "rows_scanned": rows_scanned,
                      "rows_gathered": rows_gathered,
                      "rows_full_equiv": n_eff}, probes=1)
        return float(best[k - 1])

    # -------------------------------------------------------------- stats

    def _record(self, stats: dict, *, probes: int) -> None:
        with self._lock:
            self._cum["probes"] += probes
            self._cum["launches"] += stats["launches"]
            self._cum["rows_scanned"] += stats["rows_scanned"]
            self._cum["rows_gathered"] += stats.get("rows_gathered", 0)
            self._cum["rows_full_equiv"] += stats["rows_full_equiv"]
            frac = (self._cum["rows_scanned"]
                    / max(1, self._cum["rows_full_equiv"]))
        obs = self.obs
        if obs is not None:
            obs.index_scan(stats, probes=probes, fraction=frac)

    def stats(self) -> dict:
        """Cumulative scan accounting; ``scan_fraction`` is rows actually
        streamed over rows a full-scan probe would have streamed, and
        ``rows_gathered`` the rows the kernel read, padding included."""
        with self._lock:
            d = dict(self._cum)
        d["scan_fraction"] = (d["rows_scanned"]
                              / max(1, d["rows_full_equiv"]))
        return d

    def reset_stats(self) -> None:
        with self._lock:
            for key in self._cum:
                self._cum[key] = 0


_NORM_CHUNK = 65536


def _assemble_store(x: np.ndarray, cent64: np.ndarray, assign: np.ndarray,
                    *, eps: float, chunk_rows: int,
                    perm_base: np.ndarray | None = None) -> ClusteredStore:
    """Reorder ``x`` cluster-contiguous for a given (centroids, assignment)
    and compute the exact f64 per-cluster radii (inflated by one part in
    1e9 to absorb norm roundoff — the bounds must *never* under-cover).
    ``perm_base`` relabels rows of ``x`` to external row ids (the fragment
    builder passes global ids; default is ``arange(n)``)."""
    n = x.shape[0]
    k = len(cent64)
    order = np.argsort(assign, kind="stable")
    sizes = np.bincount(assign, minlength=k).astype(np.int64)
    offsets = np.zeros(k + 1, np.int64)
    offsets[1:] = np.cumsum(sizes)
    xs = x[order]
    a_sorted = assign[order]
    # f64 norms a chunk of rows at a time: a whole-store f64 copy is twice
    # the f32 store's host memory (~9.7 GB at 1M x 1152)
    rnorm = np.empty(n, np.float64)
    row_norm = 0.0 if n else 1.0
    for s in range(0, n, _NORM_CHUNK):
        blk = xs[s:s + _NORM_CHUNK].astype(np.float64)
        rnorm[s:s + _NORM_CHUNK] = np.linalg.norm(
            blk - cent64[a_sorted[s:s + _NORM_CHUNK]], axis=1)
        row_norm = max(row_norm, float(np.linalg.norm(blk, axis=1).max()))
    radii = np.zeros(k, np.float64)
    for c in range(k):
        if sizes[c]:
            radii[c] = rnorm[offsets[c]:offsets[c + 1]].max()
    radii = radii * (1.0 + 1e-9) + 1e-12
    perm = order if perm_base is None else np.asarray(perm_base)[order]
    return ClusteredStore(
        embeddings=jnp.asarray(xs), offsets=offsets, sizes=sizes,
        centroids=np.asarray(cent64, np.float64), radii=radii,
        perm=perm.astype(np.int64), eps=eps, chunk_rows=chunk_rows,
        max_row_norm=float(row_norm) * (1.0 + 1e-9) + 1e-12)


def _split_round_2means(x64: np.ndarray, members: list[np.ndarray],
                        iters: int) -> list[np.ndarray | None]:
    """One vectorized 2-means pass over a *batch* of candidate clusters.

    Pads every candidate's member set to a common (C, M, d) stack and runs
    all C local Lloyd loops at once with masked updates — the serial
    splitter paid a jit dispatch + full Lloyd per cluster, which dominated
    build time once ``split_radius`` produced dozens of candidates.
    Seeds are deterministic farthest-point picks (member farthest from the
    mean, then the member farthest from that), so duplicates degenerate to
    an empty side immediately. Returns, per candidate, the member-index
    array of side-1 (rows to move to the new cluster) or None when the
    split is degenerate (unsplittable).
    """
    c_n = len(members)
    m_max = max(len(m) for m in members)
    d = x64.shape[1]
    pts = np.zeros((c_n, m_max, d))
    mask = np.zeros((c_n, m_max), bool)
    for i, m in enumerate(members):
        pts[i, :len(m)] = x64[m]
        mask[i, :len(m)] = True
    counts = mask.sum(axis=1)                                    # (C,)
    mean = pts.sum(axis=1) / counts[:, None]
    d_mean = np.where(mask, np.linalg.norm(pts - mean[:, None], axis=2),
                      -np.inf)
    s0 = d_mean.argmax(axis=1)
    c0 = pts[np.arange(c_n), s0]                                 # (C, d)
    d_c0 = np.where(mask, np.linalg.norm(pts - c0[:, None], axis=2),
                    -np.inf)
    c1 = pts[np.arange(c_n), d_c0.argmax(axis=1)]
    for _ in range(iters):
        d0 = np.linalg.norm(pts - c0[:, None], axis=2)           # (C, M)
        d1 = np.linalg.norm(pts - c1[:, None], axis=2)
        side1 = (d1 < d0) & mask
        side0 = ~side1 & mask
        n0 = side0.sum(axis=1)
        n1 = side1.sum(axis=1)
        ok = (n0 > 0) & (n1 > 0)
        c0 = np.where(ok[:, None],
                      (pts * side0[:, :, None]).sum(axis=1)
                      / np.maximum(n0, 1)[:, None], c0)
        c1 = np.where(ok[:, None],
                      (pts * side1[:, :, None]).sum(axis=1)
                      / np.maximum(n1, 1)[:, None], c1)
    d0 = np.linalg.norm(pts - c0[:, None], axis=2)
    d1 = np.linalg.norm(pts - c1[:, None], axis=2)
    side1 = (d1 < d0) & mask
    out: list[np.ndarray | None] = []
    for i, m in enumerate(members):
        s1 = side1[i, :len(m)]
        out.append(m[s1] if 0 < s1.sum() < len(m) else None)
    return out


def _split_fat_clusters(x: np.ndarray, cent64: np.ndarray,
                        assign: np.ndarray, *, split_radius: float,
                        max_clusters: int, seed: int = 0,
                        iters: int = 6) -> tuple[np.ndarray, np.ndarray]:
    """Recursively 2-means-split radius-outlier clusters, a *round* at a
    time.

    Lloyd's local optima merge concept clumps into one wide cluster that
    straddles every probe's boundary (docs/index.md pathology); splitting
    restores tight radii without oversegmenting the rest of the store.
    Each round collects every cluster with radius > ``split_radius`` and
    >= 2 members (widest first when ``max_clusters`` caps how many can
    split), runs ONE vectorized 2-means over the whole batch
    (``_split_round_2means``), and re-queues still-fat children for the
    next round. A degenerate split (all members on one side, e.g.
    duplicated points) marks the cluster unsplittable, so the loop always
    terminates. Only the assignment changes; bounds stay exact because
    radii are recomputed from the actual members downstream. ``seed`` is
    kept for signature stability — seeding is deterministic farthest-point
    now, so it is unused.
    """
    del seed
    x64 = x.astype(np.float64)
    cents = [c for c in np.asarray(cent64, np.float64)]
    assign = np.asarray(assign).copy()
    unsplittable: set[int] = set()
    # (radius, members) cache — only split children change between rounds
    info: dict[int, tuple[float, np.ndarray]] = {}

    def refresh(c: int) -> None:
        m = np.flatnonzero(assign == c)
        r = float(np.linalg.norm(x64[m] - cents[c], axis=1).max()) \
            if len(m) else 0.0
        info[c] = (r, m)

    for c in range(len(cents)):
        refresh(c)
    while len(cents) < max_clusters:
        cand = sorted(
            ((r, c, m) for c, (r, m) in info.items()
             if r > split_radius and len(m) >= 2 and c not in unsplittable),
            key=lambda e: -e[0])[:max_clusters - len(cents)]
        if not cand:
            break
        moves = _split_round_2means(x64, [m for _, _, m in cand], iters)
        progressed = False
        for (_, c, m), mv in zip(cand, moves):
            if mv is None:
                unsplittable.add(c)
                continue
            new_id = len(cents)
            cents.append(cents[c].copy())       # placeholder; refreshed below
            assign[mv] = new_id
            keep = np.setdiff1d(m, mv, assume_unique=True)
            cents[c] = x64[keep].mean(axis=0)
            cents[new_id] = x64[mv].mean(axis=0)
            refresh(c)
            refresh(new_id)
            progressed = True
            if len(cents) >= max_clusters:
                break
        if not progressed:
            break
    return np.asarray(cents), assign


def build_clustered_store(
    embeddings: np.ndarray, k_clusters: int, *, iters: int = 8,
    seed: int = 0, impl: str = "pallas", interpret: bool | None = None,
    eps: float = 1e-4, chunk_rows: int = 4096,
    split_radius: float | None = None, max_clusters: int | None = None,
    init_centroids: np.ndarray | None = None,
) -> ClusteredStore:
    """Partition (N, d) embeddings into K clusters for pruned probing.

    Runs Lloyd's k-means (the existing ``repro.kernels.kmeans`` kernel),
    reorders the store cluster-contiguous, and computes per-cluster radii in
    float64. K is clamped to N; empty clusters get zero-width segments and
    are skipped by every probe.

    ``split_radius``: after Lloyd's converges, recursively split every
    cluster whose radius exceeds this budget with a local 2-means
    (widest-first) until all clusters fit the budget, turn out
    unsplittable, or the total hits ``max_clusters`` (default ``4 * K``,
    clamped to N). Splitting only refines the partition — probes stay
    bitwise equal to the full scan — but turns the fat-cluster pathology
    (one wide cluster boundary for every probe) into tight segments bounds
    can actually prune. See docs/index.md.

    ``init_centroids``: warm-start Lloyd's from a previous build's centroids
    (the incremental rebuild path) — a couple of refinement iterations then
    recover a cold run's partition quality at a fraction of the cost, since
    most rows keep their assignment across a small mutation batch.
    """
    x = np.asarray(embeddings, np.float32)
    n, d = x.shape
    k = max(1, min(int(k_clusters), n))
    centroids, assign = kmeans(x, k, iters=iters, seed=seed, impl=impl,
                               interpret=interpret,
                               init_centroids=init_centroids)
    cent64 = centroids.astype(np.float64)
    if split_radius is not None and split_radius > 0:
        cap = min(n, 4 * k if max_clusters is None else int(max_clusters))
        cent64, assign = _split_fat_clusters(
            x, cent64, assign, split_radius=float(split_radius),
            max_clusters=max(k, cap), seed=seed)
    return _assemble_store(x, cent64, assign, eps=eps, chunk_rows=chunk_rows)


def store_from_fragments(
    embeddings: np.ndarray, fragments: list[tuple[np.ndarray, np.ndarray]],
    *, eps: float = 1e-4, chunk_rows: int = 4096,
) -> ClusteredStore:
    """Build a ``ClusteredStore`` whose clusters are exactly the given
    ``(row_ids, centroid)`` fragments — no k-means run.

    The boundary-balanced sharded build (``repro.index.sharded``) clusters
    the store *globally*, packs clusters onto shards by boundary mass, and
    hands each shard its assigned fragments; this constructor turns one
    shard's fragments into a local sub-index. ``row_ids`` index into
    ``embeddings`` and must be disjoint across fragments; ``perm`` carries
    them through, so the sub-index remembers each row's external id. Radii
    are recomputed exactly over each fragment's actual members (a fragment
    of a split cluster is at most as wide as its parent), so bounds stay
    exact.
    """
    x = np.asarray(embeddings, np.float32)
    rows = np.concatenate([np.asarray(r, np.int64) for r, _ in fragments]) \
        if fragments else np.empty(0, np.int64)
    assign = np.concatenate(
        [np.full(len(r), i, np.int64) for i, (r, _) in enumerate(fragments)]
    ) if fragments else np.empty(0, np.int64)
    cent64 = np.asarray([c for _, c in fragments], np.float64) \
        if fragments else np.empty((0, x.shape[1]), np.float64)
    return _assemble_store(x[rows], cent64, assign, eps=eps,
                           chunk_rows=chunk_rows, perm_base=rows)
