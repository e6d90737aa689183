"""Per-shard cluster-pruned index: sublinear probes that survive sharding.

PR 3's ``ClusteredStore`` made single-device probes sublinear at low
selectivity, but the pod-scale path (``make_sharded_probe``) still streamed
every shard end to end — the two headline subsystems were mutually
exclusive. This module shards the index itself:

  partition    the (N, d) store is split into ``n_shards`` contiguous row
               blocks — the SAME partition ``NamedSharding(mesh,
               P(('pod','data')))`` induces, so shard s's sub-index
               describes exactly the rows device s holds. Each block gets
               its own k-means partition (a ``ClusteredStore`` over the
               local slice): cluster-contiguous local layout, f64 centroids
               and radii *per shard*.

  why per-shard radii   a global clustering would scatter a cluster's
               members across shards, so a boundary cluster would drag
               every shard into the scan. Clustering each shard's rows
               independently keeps segments local (a boundary segment is
               one contiguous slice of one device's memory) and lets the
               bound classification prune *per shard* — shards whose local
               clusters all resolve by bounds contribute zero scanned rows
               to the launch, which is how scan fraction stays sublinear at
               pod scale and how boundary work imbalance becomes visible
               (see ``stats()['per_shard']``).

  probe        ``repro.core.histogram.make_sharded_pruned_probe`` plans all
               shards on the host (exact Cauchy-Schwarz bounds, f64 — jax
               x64 is off, so bound arithmetic cannot live in the traced
               body), gathers each shard's boundary segments into a common
               power-of-two bucket, and launches ONE shard_map whose body
               scans only the local bucket via the masked cosine_topk
               kernels, then does the existing O(B*k) psum / all-gather
               combine. Counts and top-k stay bitwise equal to the
               full-scan sharded path.

Stats: every shard's sub-index keeps its own thread-safe scan accounting
(rows it actually streamed vs the rows a full shard scan would), aggregated
by ``stats()`` with a ``per_shard`` breakdown plus the canonical
``spread`` / ``max_scan_fraction`` fields — uneven boundary work across
shards is the perf surface this module's *build* now optimizes.

Boundary-mass balancing (PR 5): the shard_map bucket is uniform (one shape
across shards), so every probe pays the **max** per-shard boundary rows —
the min-max cost the contiguous build leaves to chance. With
``balance="boundary"`` the build clusters the store *globally* (after
fat-cluster splitting), scores each cluster's expected boundary mass
(``size x radius``: a random threshold cuts a cluster with probability
proportional to its radius and pays its size in rows when it does), and
packs clusters onto shards with a greedy LPT min-max packer under the hard
equal-rows-per-shard constraint — splitting clusters at shard edges when
packing requires it (``perm`` makes any reordering result-invariant, and a
fragment's radius is recomputed from its actual members, so bounds stay
exact). Probes are bitwise unchanged; only *where* boundary rows live
moves, which is exactly what the max-over-shards launch cost measures.
"""

from __future__ import annotations

import dataclasses
import heapq
import threading

import jax.numpy as jnp
import numpy as np

from repro.index.clustered import (
    ClusteredStore,
    build_clustered_store,
    gather_rows,
    store_from_fragments,
)

__all__ = ["ShardedClusteredStore", "build_sharded_clustered_store"]


@dataclasses.dataclass
class ShardedClusteredStore:
    """One ``ClusteredStore`` per contiguous shard row-block of the store.

    ``embeddings`` is the reordered (N, d) store: shard blocks in order,
    each block cluster-contiguous; place it with the mesh's data sharding
    and every device holds exactly its sub-index's rows. ``perm`` maps
    reordered row -> original row id (counts and top-k distances are
    permutation-invariant, so results are interchangeable with any scan of
    the original store). Attach to ``SemanticHistogram(mesh=..., index=...)``
    to route every probe through the pruned sharded path.
    """

    shards: list[ClusteredStore]   # per-shard sub-index over its row block
    shard_rows: int                # rows per shard (uniform)
    embeddings: np.ndarray         # (N, d) f32, shard-blocked + reordered;
    #                                kept on the host — the probe places it
    #                                on the mesh, one row block per device
    perm: np.ndarray               # (N,) original row ids in stored order
    balance: str = "contiguous"    # partitioning strategy used at build
    # predicted per-shard boundary mass of the *contiguous* row-block
    # partition under the balanced build's global clustering — the
    # counterfactual serve prints next to boundary_mass() (balanced builds
    # only; None for contiguous builds, which have no global clustering)
    contiguous_mass: np.ndarray | None = None
    # warm-start state for the incremental rebuild (boundary builds only):
    # the global clustering's centroids, handed back to the next build as
    # ``init_centroids`` so Lloyd's refines instead of restarting cold
    global_centroids: np.ndarray | None = None

    def __post_init__(self):
        self.n = int(self.embeddings.shape[0])
        self.n_shards = len(self.shards)
        self.k_clusters = self.shards[0].k_clusters if self.shards else 0
        self.eps = self.shards[0].eps if self.shards else 1e-4
        self._lock = threading.Lock()
        self._probes = 0
        self._launches = 0
        self._rows_scanned = 0
        self._rows_full_equiv = 0
        # telemetry hub, attached by the serve layer to the WRAPPER only
        # (per-shard stores keep obs=None so a probe emits once)
        self.obs = None

    # ------------------------------------------------------------ planning

    def plan_shards(self, preds: np.ndarray, thr: np.ndarray, *, k: int,
                    need_topk: bool = True,
                    live_sizes: list | None = None) -> list:
        """One exact ``ScanPlan`` per shard for a (B, d) x (B, T) probe.

        ``k`` is the per-shard top-k cover size (the combine gathers that
        many candidates per shard), already clamped by the caller to the
        shard row count. ``live_sizes`` — one (K_s,) per-cluster live count
        array per shard (mutable-store tombstones) — makes each shard plan
        over its live rows only.
        """
        if live_sizes is None:
            live_sizes = [None] * self.n_shards
        return [s.plan_scan(preds, thr, k=k, need_topk=need_topk,
                            live_sizes=ls)
                for s, ls in zip(self.shards, live_sizes)]

    def count_bounds(self, preds: np.ndarray, thresholds: np.ndarray, *,
                     live_sizes: list | None = None,
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Exact count interval per (predicate, threshold) — zero rows read.

        Sums each shard's bound-only interval (host-side; no mesh needed),
        so the sharded index supports the same degraded-mode answers as the
        single-device one. lo <= true count <= hi, per shard and in total.
        ``live_sizes`` as in ``plan_shards`` — intervals then certify the
        live subset.
        """
        if live_sizes is None:
            live_sizes = [None] * self.n_shards
        los, his = zip(*(s.count_bounds(preds, thresholds, live_sizes=ls)
                         for s, ls in zip(self.shards, live_sizes)))
        return sum(los), sum(his)

    # ----------------------------------------------------------- compound

    def probe_compound(self, preds: np.ndarray, thresholds: np.ndarray, *,
                       mode: str = "and", live: list | None = None,
                       live_sizes: list | None = None,
                       live_n: list | None = None) -> tuple[int, dict]:
        """Exact compound match count across all shards.

        Each shard plans the conjunction/disjunction jointly
        (``ClusteredStore.plan_compound`` — per-conjunct all-in/all-out
        sets intersected before any boundary scan), gathers only its
        surviving boundary segments into an explicit power-of-two bucket,
        and scores them through the same masked XLA launch as the
        single-device path; per-shard counts and bound-resolved extras sum.
        Per-row distances are row-local, so the shard decomposition is
        bitwise-invariant vs one scan of the whole store.

        ``live``/``live_sizes``/``live_n``: one per-shard entry each
        (mutable-store tombstones), as in ``plan_shards``/``record``.
        Returns (count, stats) — stats aggregated across shards with the
        same keys as ``ClusteredStore.probe_compound``.
        """
        from repro.index.clustered import _compound_masked_xla

        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32).reshape(-1)
        if live is None:
            live = [None] * self.n_shards
        if live_sizes is None:
            live_sizes = [None] * self.n_shards
        plans = [s.plan_compound(preds, thr, mode=mode, live_sizes=ls)
                 for s, ls in zip(self.shards, live_sizes)]
        count = sum(int(p.extra[0, 0]) for p in plans)
        rows_scanned = 0
        gathered = [0] * self.n_shards
        for s, (shard, plan, lv) in enumerate(zip(self.shards, plans, live)):
            if not (len(plan.scan_ids) and plan.m):
                continue
            rows = shard.scan_rows(plan.scan_ids, lv)
            m = int(len(rows))
            rows_scanned += m
            bucket = max(128, 1 << max(0, m - 1).bit_length())
            gathered[s] = bucket
            pad = np.zeros(bucket - m, np.int64)
            buf = gather_rows(shard.embeddings,
                              jnp.asarray(np.concatenate([rows, pad])))
            count += int(_compound_masked_xla(
                buf, jnp.asarray(m, jnp.int32), jnp.asarray(preds),
                jnp.asarray(thr), mode=mode))
        launched = rows_scanned > 0
        self.record(plans, launched=launched, live_n=live_n,
                    gathered=gathered)
        nl = live_n if live_n is not None else [s.n for s in self.shards]
        n_eff = sum(int(x) for x in nl)
        stats = {
            "launches": 1 if launched else 0,
            "rows_scanned": rows_scanned,
            "rows_full_equiv": n_eff,
            "scan_fraction": rows_scanned / max(1, n_eff),
            "scanned_clusters": sum(len(p.scan_ids) for p in plans),
            "boundary_clusters": sum(p.boundary_clusters for p in plans),
            "clusters": sum(s.k_clusters for s in self.shards),
            "batch": int(preds.shape[0]),
        }
        return count, stats

    # -------------------------------------------------------------- stats

    def record(self, plans: list, *, launched: bool,
               live_n: list | None = None,
               gathered: list | None = None) -> None:
        """Account one sharded probe: per-shard rows into each sub-index
        (their scan fractions diverge when boundary work is uneven), the
        probe/launch tally here. ``live_n`` — per-shard live row counts
        under tombstones — replaces ``shard.n`` as the full-scan-equivalent
        denominator. ``gathered`` — per-shard rows the kernel read, the
        power-of-two bucket with its padding — defaults to each shard's
        scanned rows."""
        if live_n is None:
            live_n = [s.n for s in self.shards]
        if gathered is None:
            gathered = [p.m for p in plans]
        for shard, plan, nl, g in zip(self.shards, plans, live_n, gathered):
            shard._record({"launches": 1 if (launched and plan.m) else 0,
                           "rows_scanned": plan.m if launched else 0,
                           "rows_gathered": int(g) if launched else 0,
                           "rows_full_equiv": int(nl)}, probes=1)
        rows = sum(p.m for p in plans) if launched else 0
        full = sum(int(nl) for nl in live_n)
        with self._lock:
            self._probes += 1
            self._launches += 1 if launched else 0
            self._rows_scanned += rows
            self._rows_full_equiv += full
            frac = self._rows_scanned / max(1, self._rows_full_equiv)
        obs = self.obs
        if obs is not None:
            obs.index_scan(
                {"launches": 1 if launched else 0, "rows_scanned": rows,
                 "rows_full_equiv": full,
                 "scan_fraction": rows / max(1, full)},
                probes=1, fraction=frac,
                per_shard=[{"shard": s,
                            "rows_scanned": int(p.m) if launched else 0,
                            "rows_full_equiv": int(nl)}
                           for s, (p, nl) in
                           enumerate(zip(plans, live_n))])

    def boundary_mass(self) -> np.ndarray:
        """Predicted boundary mass per shard: ``sum(size_c * radius_c)``
        over each shard's clusters — the build-time proxy for how many rows
        a threshold landing uniformly at random forces that shard to scan.
        The balanced build minimizes the max of exactly this vector."""
        return np.asarray([float((s.sizes * s.radii).sum())
                           for s in self.shards])

    def stats(self) -> dict:
        """Aggregate scan accounting + ``per_shard`` breakdown.

        ``launches`` counts shard_map launches (one per probe that scanned
        anything anywhere); ``per_shard[s]['scan_fraction']`` is shard s's
        rows streamed over the rows a full shard scan would have streamed.
        ``spread`` (max - min per-shard scan fraction) and
        ``max_scan_fraction`` are the canonical imbalance fields — the
        uniform shard_map bucket makes every probe pay the *max* shard's
        boundary rows, so ``max_scan_fraction`` is what a probe actually
        costs and ``spread`` is the headroom rebalancing can recover.
        ``max_shard_rows_scanned`` is the same max in absolute rows.
        """
        per = [s.stats() for s in self.shards]
        with self._lock:
            d = {"probes": self._probes, "launches": self._launches}
        d["rows_scanned"] = sum(p["rows_scanned"] for p in per)
        d["rows_gathered"] = sum(p["rows_gathered"] for p in per)
        d["rows_full_equiv"] = sum(p["rows_full_equiv"] for p in per)
        d["scan_fraction"] = (d["rows_scanned"]
                              / max(1, d["rows_full_equiv"]))
        d["per_shard"] = [{"rows_scanned": p["rows_scanned"],
                           "rows_full_equiv": p["rows_full_equiv"],
                           "scan_fraction": p["scan_fraction"]}
                          for p in per]
        fracs = [p["scan_fraction"] for p in d["per_shard"]]
        d["max_scan_fraction"] = max(fracs, default=0.0)
        d["spread"] = (max(fracs) - min(fracs)) if fracs else 0.0
        d["max_shard_rows_scanned"] = max(
            (p["rows_scanned"] for p in d["per_shard"]), default=0)
        return d

    def reset_stats(self) -> None:
        for s in self.shards:
            s.reset_stats()
        with self._lock:
            self._probes = 0
            self._launches = 0
            self._rows_scanned = 0
            self._rows_full_equiv = 0


def _cluster_items(gcs: ClusteredStore) -> list:
    """Per-cluster pack items ``(-mass, tiebreak, members, dist, cent)``:
    member ids (global row ids) sorted near-to-far plus the matching
    centroid distances, so fragment masses need no re-norm pass. Max-heap
    order on boundary mass ``size x radius``."""
    xs = np.asarray(gcs.embeddings, np.float64)   # one host copy, not K
    items = []
    tiebreak = 0
    for c in range(gcs.k_clusters):
        if not gcs.sizes[c]:
            continue
        members = gcs.perm[gcs.offsets[c]:gcs.offsets[c + 1]]
        seg = xs[gcs.offsets[c]:gcs.offsets[c + 1]]
        dist = np.linalg.norm(seg - gcs.centroids[c], axis=1)
        order = np.argsort(dist, kind="stable")
        members, dist = members[order], dist[order]
        items.append((-float(len(members) * dist[-1]), tiebreak,
                      members, dist, gcs.centroids[c]))
        tiebreak += 1
    return items


def _lpt_place(items: list, cap: list, load: list, frags: list) -> None:
    """Core greedy LPT loop: pop the heaviest item, place it on the
    lightest shard with row capacity left, split at the shard edge when it
    does not fit (near core fills the shard — tight fragment radius — and
    the far shell re-enters the worklist with its own, smaller-or-equal,
    mass). ``items`` is a max-heap on mass, ``load`` a min-heap of
    ``(mass, shard)``; both are consumed in place, ``frags`` accumulates
    per-shard ``(global_row_ids, centroid)`` fragments."""
    tiebreak = -1          # negative tiebreaks cannot collide with items'
    while items:
        neg_mass, _, members, dist, cent = heapq.heappop(items)
        # lightest shard with capacity (full shards drop out of the heap)
        while cap[load[0][1]] == 0:
            heapq.heappop(load)
        mass, s = heapq.heappop(load)
        take = min(len(members), cap[s])
        frags[s].append((members[:take], cent))
        cap[s] -= take
        placed_mass = float(take * dist[take - 1])  # fragment's own radius
        heapq.heappush(load, (mass + placed_mass, s))
        if take < len(members):                     # far shell re-enters
            rest, rdist = members[take:], dist[take:]
            heapq.heappush(items, (-float(len(rest) * rdist[-1]), tiebreak,
                                   rest, rdist, cent))
            tiebreak -= 1


def _pack_boundary_balanced(
    gcs: ClusteredStore, n_shards: int, rows: int,
) -> list[list[tuple[np.ndarray, np.ndarray]]]:
    """Greedy LPT min-max pack of global clusters onto shards.

    Items are the global store's clusters scored by boundary mass
    ``size x radius``; each is assigned whole to the currently-lightest
    shard with row capacity left (longest-processing-time order), and when
    the lightest shard cannot hold a whole cluster the cluster is *split at
    the shard edge* (see ``_lpt_place``). Row capacities sum to N, so
    packing always completes with every shard exactly full. Returns
    per-shard ``(global_row_ids, centroid)`` fragment lists.
    """
    items = _cluster_items(gcs)
    heapq.heapify(items)
    cap = [rows] * n_shards
    load = [(0.0, s) for s in range(n_shards)]      # min-heap on mass
    heapq.heapify(load)
    frags: list[list[tuple[np.ndarray, np.ndarray]]] = \
        [[] for _ in range(n_shards)]
    _lpt_place(items, cap, load, frags)
    return frags


def _pack_boundary_incremental(
    gcs: ClusteredStore, n_shards: int, rows: int,
    shard_hint: np.ndarray, *, tol: float = 0.25,
) -> list[list[tuple[np.ndarray, np.ndarray]]]:
    """Hint-guided LPT pack: keep clusters where their rows already live.

    ``shard_hint`` (N,) gives each global row its *previous* generation's
    shard (-1 for rows with no prior placement, e.g. fresh ingests). A full
    repack moves most of the store between shards on every rebuild even
    when only a few percent of rows changed; this variant first pins each
    cluster to the shard that already holds the majority of its members —
    accepted while that shard has row capacity and its boundary mass stays
    within ``(1 + tol)`` of the ideal (total mass / n_shards) — and only
    the overflow (clusters whose hinted shard is full or overweight, plus
    edge-split shells) goes through the normal LPT pass over the remaining
    capacity. Same exactness story as the balanced pack: ``perm`` makes any
    placement result-invariant; only the max per-shard mass and the row
    movement differ.
    """
    items = _cluster_items(gcs)
    items.sort()                                   # heaviest first (-mass)
    total_mass = -sum(it[0] for it in items)
    budget = (1.0 + tol) * total_mass / n_shards
    cap = [rows] * n_shards
    mass = [0.0] * n_shards
    frags: list[list[tuple[np.ndarray, np.ndarray]]] = \
        [[] for _ in range(n_shards)]
    leftovers = []
    hint = np.asarray(shard_hint, np.int64)
    for it in items:
        _, tiebreak, members, dist, cent = it
        prev = hint[members]
        prev = prev[prev >= 0]
        s = int(np.bincount(prev, minlength=n_shards).argmax()) \
            if len(prev) else -1
        if s < 0 or cap[s] == 0 or mass[s] >= budget:
            leftovers.append(it)
            continue
        take = min(len(members), cap[s])
        frags[s].append((members[:take], cent))
        cap[s] -= take
        mass[s] += float(take * dist[take - 1])
        if take < len(members):                     # shell -> LPT phase
            rest, rdist = members[take:], dist[take:]
            leftovers.append((-float(len(rest) * rdist[-1]), tiebreak,
                              rest, rdist, cent))
    heapq.heapify(leftovers)
    load = [(mass[s], s) for s in range(n_shards)]
    heapq.heapify(load)
    _lpt_place(leftovers, cap, load, frags)
    return frags


def build_sharded_clustered_store(
    embeddings: np.ndarray, k_clusters: int, n_shards: int, *,
    iters: int = 8, seed: int = 0, impl: str = "pallas",
    interpret: bool | None = None, eps: float = 1e-4, chunk_rows: int = 4096,
    balance: str = "contiguous", split_radius: float | None = None,
    max_clusters: int | None = None,
    init_centroids: np.ndarray | None = None,
    shard_hint: np.ndarray | None = None,
) -> ShardedClusteredStore:
    """Partition the store into ``n_shards`` equal row blocks of K clusters.

    The block partition matches ``NamedSharding(mesh, P(('pod','data')))``
    row-major device order, so the reordered store can be placed on the
    mesh and every device's slice is exactly its sub-index. ``k_clusters``
    is per shard (size per-shard K by the local row count: K ~ sqrt(N/S)).
    N must divide evenly — jax requires the same for the sharded store.

    ``balance`` picks the partitioning strategy:

    * ``"contiguous"`` (default, PR 4): each shard is whatever contiguous
      row block the *original order* happens to give it, clustered locally
      (per-shard k-means seeds differ so identical shard contents don't
      collapse to identical local optima). Ingest order that groups rows by
      concept concentrates a clump's boundary mass on whichever shards hold
      it — and the uniform shard_map bucket makes every probe pay the max.
    * ``"boundary"``: cluster globally (``k_clusters * n_shards`` clusters,
      post fat-cluster splitting), score each cluster's boundary mass
      (``size x radius``), and greedily pack clusters onto shards to
      minimize the max per-shard mass under the hard equal-rows constraint
      (clusters split at shard edges when packing requires it — see
      ``_pack_boundary_balanced``). Counts/top-k stay bitwise equal to any
      other partition: ``perm`` makes reordering result-invariant.

    ``split_radius`` (either mode) forwards to the fat-cluster splitter.

    Incremental rebuild knobs (``balance="boundary"`` only — the mutable
    store's background rebuild path): ``init_centroids`` warm-starts the
    global k-means from the prior generation's ``global_centroids`` (fewer
    Lloyd iterations recover a cold build's partition), and ``shard_hint``
    (N,) int64 — each row's previous shard, -1 for new rows — switches the
    packer to ``_pack_boundary_incremental`` so clusters stay on the shard
    that already holds their rows unless balance demands otherwise.
    """
    x = np.asarray(embeddings, np.float32)
    n = x.shape[0]
    if n_shards < 1 or n % n_shards:
        raise ValueError(
            f"store rows ({n}) must divide evenly into n_shards "
            f"({n_shards}) — same constraint as the mesh sharding")
    rows = n // n_shards
    if not 1 <= int(k_clusters) <= rows:
        raise ValueError(
            f"k_clusters={k_clusters} must be in [1, shard_rows={rows}] — "
            f"each shard holds {rows} rows ({n} rows / {n_shards} shards) "
            f"and k-means cannot place more centroids than rows")
    if balance not in ("contiguous", "boundary"):
        raise ValueError(f"balance={balance!r}: expected 'contiguous' or "
                         f"'boundary'")
    if balance != "boundary" and (init_centroids is not None
                                  or shard_hint is not None):
        raise ValueError("init_centroids / shard_hint warm-start requires "
                         "balance='boundary' (per-shard k-means runs have "
                         "no global clustering to warm-start)")

    if balance == "boundary":
        gcs = build_clustered_store(
            x, int(k_clusters) * n_shards, iters=iters, seed=seed,
            impl=impl, interpret=interpret, eps=eps, chunk_rows=chunk_rows,
            split_radius=split_radius, max_clusters=max_clusters,
            init_centroids=init_centroids)
        # counterfactual: the contiguous row-block partition's predicted
        # mass under the same global clustering (each row contributes its
        # cluster's radius to the block that holds it)
        cluster_of = np.empty(n, np.int64)
        cluster_of[gcs.perm] = np.repeat(np.arange(gcs.k_clusters),
                                         gcs.sizes)
        contiguous_mass = gcs.radii[cluster_of].reshape(n_shards,
                                                        rows).sum(axis=1)
        if shard_hint is not None:
            frags = _pack_boundary_incremental(
                gcs, n_shards, rows, np.asarray(shard_hint, np.int64))
        else:
            frags = _pack_boundary_balanced(gcs, n_shards, rows)
        shards, perm, parts = [], [], []
        for s in range(n_shards):
            cs = store_from_fragments(x, frags[s], eps=eps,
                                      chunk_rows=chunk_rows)
            shards.append(cs)
            perm.append(cs.perm)        # already global row ids
            parts.append(np.asarray(cs.embeddings))
        return ShardedClusteredStore(
            shards=shards, shard_rows=rows,
            embeddings=np.concatenate(parts),
            perm=np.concatenate(perm), balance="boundary",
            contiguous_mass=contiguous_mass,
            global_centroids=np.asarray(gcs.centroids, np.float64))

    shards, perm, parts = [], [], []
    for s in range(n_shards):
        cs = build_clustered_store(
            x[s * rows:(s + 1) * rows], k_clusters, iters=iters,
            seed=seed + s, impl=impl, interpret=interpret, eps=eps,
            chunk_rows=chunk_rows, split_radius=split_radius,
            max_clusters=max_clusters)
        shards.append(cs)
        perm.append(s * rows + cs.perm)
        parts.append(np.asarray(cs.embeddings))
    return ShardedClusteredStore(
        shards=shards, shard_rows=rows,
        embeddings=np.concatenate(parts),
        perm=np.concatenate(perm))
