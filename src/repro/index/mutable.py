"""Mutable clustered store: streaming ingest over the exact pruned index.

The clustered index (``clustered.py``/``sharded.py``) is built once over a
frozen store; real serving workloads ingest new images and retire old ones
continuously. This module makes the store mutable WITHOUT giving up the
repo's headline invariant — every probe stays bitwise equal to a fresh full
scan of the live rows:

  hot tail     inserts append to an unindexed buffer that every probe scans
               fully through the rowmask cosine_topk kernels (or their jnp
               twins). A full scan of the tail is exact by construction, and
               the per-row distance is row-local (the reduction is over d
               only), so base counts + tail counts and a sorted merge of
               the two exact top-k candidate sets reproduce the fresh
               full-scan outputs bit for bit.

  tombstones   deletes flip a per-row live flag. Live rows are a subset of
               each cluster's build-time members, so the exact
               Cauchy-Schwarz bounds stay valid for the live subset:
               all-in clusters contribute their *live* count, and dead rows
               are excluded at gather time (``ClusteredStore.scan_rows``'s
               ``live`` mask), never entering a scan buffer.

  rebuild      mutations degrade the index (the tail is a full-scan tax;
               tombstones inflate effective radii). When the live tail
               fraction, the dead-row fraction, or the max per-cluster
               radius inflation crosses its threshold, a background thread
               rebuilds the base over the live rows — warm-started from
               the previous generation's centroids and (sharded) shard
               assignment, so an incremental rebuild costs a fraction of a
               cold build — and swaps the new index in atomically under the
               serve loop. The lock is held only to snapshot and to swap;
               probes proceed against the old generation throughout the
               heavy build. Deletes landing mid-rebuild are re-applied as
               tombstones in the new base at swap; inserts landing
               mid-rebuild simply stay in the (new) tail.

  generations  ``generation`` bumps once per swap, ``version`` once per
               mutation batch *and* per swap. The predicate cache keys on
               ``version`` (see ``PredicateCache.key``), so a cached count
               can never be served across a mutation that changed it.

Sharded mode (``mesh=``): the base is a ``ShardedClusteredStore`` probed
through ``make_sharded_pruned_probe`` with per-shard live masks; the tail
is host-side and unsharded (it is small by the rebuild trigger), scanned by
the same local kernels. Because jax's sharded placement needs equal rows
per shard, a rebuild keeps ``n_live % n_shards`` remainder rows in the new
tail — the equal-rows constraint holds at every generation by construction.
"""

from __future__ import annotations

import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.clustered import build_clustered_store
from repro.index.sharded import build_sharded_clustered_store
from repro.kernels.cosine_topk.ref import HIGHEST

f32 = jnp.float32

__all__ = ["MutableClusteredStore"]


@partial(jax.jit, static_argnames=("k",))
def _tail_probe_xla(store, mask, pred, thr, *, k: int):
    """Scalar rowmask tail scan — mirrors ``histogram._local_probe``'s
    ``nd,d->n`` contraction so tail rows' distances are bitwise the
    distances a fresh full scalar scan computes for them."""
    sims = jnp.einsum("nd,d->n", store.astype(f32), pred.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(mask != 0, 1.0 - sims, jnp.inf)
    counts = (dists[None, :] <= thr[:, None]).sum(axis=1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts.astype(jnp.int32), -neg_top


@partial(jax.jit, static_argnames=("k",))
def _tail_probe_batch_xla(store, mask, preds, thr, *, k: int):
    """Batched twin (``nd,bd->bn``, matching ``_local_probe_batch``)."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(mask[None, :] != 0, 1.0 - sims, jnp.inf)
    counts = (dists[:, None, :] <= thr[:, :, None]).sum(axis=-1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts.astype(jnp.int32), -neg_top


@partial(jax.jit, static_argnames=("mode",))
def _tail_compound_xla(store, mask, preds, thr, *, mode: str):
    """Compound rowmask tail scan — same ``nd,bd->bn`` contraction as
    ``clustered._compound_masked_xla``, with tombstoned (and padding) rows
    masked to +inf so they match no conjunct under either mode."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(mask[None, :] != 0, 1.0 - sims, jnp.inf)
    match = dists <= thr[:, None]
    hit = match.all(axis=0) if mode == "and" else match.any(axis=0)
    return hit.sum().astype(jnp.int32)


class MutableClusteredStore:
    """Streaming-mutable wrapper over the exact cluster-pruned index.

    Attach to ``SemanticHistogram(index=...)`` (with ``mesh=`` for the
    sharded base) and every probe routes through ``probe`` here — exact
    under any interleaving of ``insert`` / ``delete`` / rebuild. External
    row ids are stable: the initial store's rows get ids ``0..N-1`` and
    ``insert`` returns fresh ids; ``delete`` takes ids.

    Rebuild triggers (checked after every mutation when ``auto_rebuild``):
    live-tail fraction >= ``rebuild_tail_frac``, dead-row fraction >=
    ``rebuild_dead_frac``, or max per-cluster radius inflation (built
    radius over live max centroid distance) >= ``rebuild_inflation``.
    ``incremental=True`` warm-starts the rebuild from the previous
    generation (``rebuild_iters`` Lloyd refinements instead of a cold
    ``iters``-iteration run, plus the hint-guided shard pack).
    """

    is_mutable = True

    def __init__(self, embeddings: np.ndarray, k_clusters: int, *,
                 mesh=None, impl: str = "xla", interpret: bool | None = None,
                 iters: int = 8, seed: int = 0,
                 split_radius: float | None = None,
                 max_clusters: int | None = None,
                 eps: float = 1e-4, chunk_rows: int = 4096,
                 rebuild_tail_frac: float = 0.25,
                 rebuild_dead_frac: float = 0.25,
                 rebuild_inflation: float = 4.0,
                 incremental: bool = True, rebuild_iters: int = 2,
                 auto_rebuild: bool = True):
        x = np.asarray(embeddings, np.float32)
        if x.ndim != 2 or not len(x):
            raise ValueError(f"embeddings must be (N, d), got {x.shape}")
        self.d = int(x.shape[1])
        self.impl = impl
        self.interpret = interpret
        self.iters = int(iters)
        self.seed = int(seed)
        self.split_radius = split_radius
        self.eps = float(eps)
        self.chunk_rows = int(chunk_rows)
        self.rebuild_tail_frac = float(rebuild_tail_frac)
        self.rebuild_dead_frac = float(rebuild_dead_frac)
        self.rebuild_inflation = float(rebuild_inflation)
        self.incremental = bool(incremental)
        self.rebuild_iters = int(rebuild_iters)
        self.auto_rebuild = bool(auto_rebuild)
        self.mesh = mesh
        self._k_clusters = int(k_clusters)
        self._max_clusters = max_clusters

        if mesh is not None:
            from repro.core.histogram import _mesh_data_axes

            self._data_axes = _mesh_data_axes(mesh)
            n_shards = 1
            for a in self._data_axes:
                n_shards *= mesh.shape[a]
            self._n_shards = n_shards
            if len(x) % n_shards:
                raise ValueError(
                    f"initial store rows ({len(x)}) must divide the mesh's "
                    f"{n_shards} data shards evenly (later generations keep "
                    f"the remainder in the tail automatically)")
            base = build_sharded_clustered_store(
                x, self._k_clusters, n_shards, iters=self.iters,
                seed=self.seed, impl=impl, interpret=interpret, eps=eps,
                chunk_rows=chunk_rows, balance="boundary",
                split_radius=split_radius, max_clusters=max_clusters)
        else:
            self._n_shards = 1
            base = build_clustered_store(
                x, self._k_clusters, iters=self.iters, seed=self.seed,
                impl=impl, interpret=interpret, eps=eps,
                chunk_rows=chunk_rows, split_radius=split_radius,
                max_clusters=max_clusters)

        self._lock = threading.RLock()
        self.version = 0
        self.generation = 0
        self.inserts = 0
        self.deletes = 0
        self.rebuilds = 0
        self.last_rebuild_s: float | None = None
        self.last_rebuild_incremental: bool | None = None
        self._rebuilding = False
        self._rebuild_thread: threading.Thread | None = None
        self._deleted_during_rebuild: set[int] = set()
        self._pre_swap_hook = None        # test hook: runs just before swap
        self._obs = None
        self._next_id = len(x)
        self._apply_state(self._prepare_state(base, np.arange(len(x))))
        self._reset_tail(np.empty((0, self.d), np.float32),
                         np.empty(0, np.int64))

    # -------------------------------------------------- state construction

    def _prepare_state(self, base, ids: np.ndarray) -> dict:
        """Everything derivable from a freshly built base — computed
        OUTSIDE the lock so the atomic swap only assigns references.
        ``ids`` maps build-input row -> external id."""
        st = {"base": base}
        st["base_ids"] = np.asarray(ids, np.int64)[base.perm]
        st["emb"] = np.asarray(base.embeddings, np.float32)
        if self.mesh is not None:
            rows = base.shard_rows
            segments = [(cs, s * rows) for s, cs in enumerate(base.shards)]
        else:
            segments = [(base, 0)]
        st["segments"] = segments
        n = st["emb"].shape[0]
        cluster_of = np.empty(n, np.int64)
        cdist = np.empty(n, np.float64)
        live_sizes, tight = [], []
        for cs, start in segments:
            cl = np.repeat(np.arange(cs.k_clusters), cs.sizes)
            cluster_of[start:start + cs.n] = cl
            xs = st["emb"][start:start + cs.n].astype(np.float64)
            cd = np.linalg.norm(xs - cs.centroids[cl], axis=1)
            cdist[start:start + cs.n] = cd
            live_sizes.append(cs.sizes.astype(np.int64).copy())
            tt = np.zeros(cs.k_clusters)
            for c in range(cs.k_clusters):
                if cs.sizes[c]:
                    tt[c] = cd[cs.offsets[c]:cs.offsets[c + 1]].max()
            tight.append(tt)
        st["cluster_of"] = cluster_of
        st["cdist"] = cdist
        st["live_sizes"] = live_sizes
        st["tight"] = tight
        st["loc"] = {int(i): ("b", p)
                     for p, i in enumerate(st["base_ids"])}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            st["placed"] = jax.device_put(
                base.embeddings,
                NamedSharding(self.mesh, PartitionSpec(self._data_axes)))
        else:
            st["placed"] = None
        return st

    def _apply_state(self, st: dict) -> None:
        self._base = st["base"]
        # re-attach the telemetry hub across generation swaps (absent
        # only during __init__'s first _apply_state call)
        self._base.obs = getattr(self, "_obs", None)
        self._base_ids = st["base_ids"]
        self._base_emb_np = st["emb"]
        self._segments = st["segments"]
        self._live = np.ones(len(st["emb"]), bool)
        self._cluster_of = st["cluster_of"]
        self._cdist = st["cdist"]
        self._live_sizes = st["live_sizes"]
        self._tight = st["tight"]
        self._base_live_n = int(len(st["emb"]))
        self._loc = st["loc"]
        self._placed = st["placed"]
        self._probe_factories = {}

    def _reset_tail(self, emb: np.ndarray, ids: np.ndarray) -> None:
        m = len(ids)
        cap = max(64, 1 << max(0, m - 1).bit_length())
        self._tail_emb = np.zeros((cap, self.d), np.float32)
        self._tail_live = np.zeros(cap, bool)
        self._tail_ids = np.zeros(cap, np.int64)
        self._tail_emb[:m] = emb
        self._tail_live[:m] = True
        self._tail_ids[:m] = ids
        self._tail_len = m
        self._tail_live_n = m
        for j, i in enumerate(ids):
            self._loc[int(i)] = ("t", j)

    # ------------------------------------------------------------ mutation

    def insert(self, embeddings: np.ndarray) -> np.ndarray:
        """Append rows to the hot tail; returns their external ids."""
        embs = np.asarray(embeddings, np.float32)
        if embs.ndim == 1:
            embs = embs[None]
        if embs.ndim != 2 or embs.shape[1] != self.d:
            raise ValueError(f"expected (m, {self.d}) rows, got "
                             f"{embs.shape}")
        m = len(embs)
        with self._lock:
            need = self._tail_len + m
            if need > len(self._tail_emb):
                cap = max(64, 1 << (need - 1).bit_length())
                for name, fill in (("_tail_emb", 0.0), ("_tail_live", False),
                                   ("_tail_ids", 0)):
                    old = getattr(self, name)
                    shape = (cap,) + old.shape[1:]
                    new = np.full(shape, fill, old.dtype)
                    new[:len(old)] = old
                    setattr(self, name, new)
            ids = np.arange(self._next_id, self._next_id + m, dtype=np.int64)
            self._next_id += m
            p0 = self._tail_len
            self._tail_emb[p0:p0 + m] = embs
            self._tail_live[p0:p0 + m] = True
            self._tail_ids[p0:p0 + m] = ids
            for j, i in enumerate(ids):
                self._loc[int(i)] = ("t", p0 + j)
            self._tail_len = need
            self._tail_live_n += m
            self.inserts += m
            self.version += 1
        if self.auto_rebuild:
            self.maybe_rebuild()
        return ids

    def delete(self, ids) -> None:
        """Tombstone rows by external id (KeyError on unknown/dead ids)."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        with self._lock:
            for i in ids:
                if int(i) not in self._loc:
                    raise KeyError(f"unknown or already-deleted id {int(i)}")
            for i in ids:
                kind, p = self._loc.pop(int(i))
                if kind == "t":
                    self._tail_live[p] = False
                    self._tail_live_n -= 1
                else:
                    self._tombstone_pos(p)
                if self._rebuilding:
                    self._deleted_during_rebuild.add(int(i))
                self.deletes += 1
            self.version += 1
        if self.auto_rebuild:
            self.maybe_rebuild()

    def _seg_index(self, p: int) -> int:
        if len(self._segments) == 1:
            return 0
        return int(p // self._base.shard_rows)

    def _tombstone_pos(self, p: int) -> None:
        """Kill one base row (lock held): live flag, per-cluster live size,
        and the cluster's tight (live-max) radius when the dead row carried
        it — the inflation trigger reads built radius / tight radius."""
        s = self._seg_index(p)
        cs, start = self._segments[s]
        self._live[p] = False
        c = int(self._cluster_of[p])
        self._live_sizes[s][c] -= 1
        self._base_live_n -= 1
        if self._cdist[p] >= self._tight[s][c] - 1e-12:
            lo, hi = start + cs.offsets[c], start + cs.offsets[c + 1]
            alive = self._live[lo:hi]
            self._tight[s][c] = (float(self._cdist[lo:hi][alive].max())
                                 if alive.any() else 0.0)

    # ------------------------------------------------------------- probing

    @property
    def n_live(self) -> int:
        with self._lock:
            return self._base_live_n + self._tail_live_n

    def _snapshot(self):
        """Consistent view for one probe (lock held only for the copies)."""
        with self._lock:
            return (self._base, self.generation, self._live.copy(),
                    [s.copy() for s in self._live_sizes],
                    self._base_live_n,
                    self._tail_emb[:self._tail_len].copy(),
                    self._tail_live[:self._tail_len].copy(),
                    self._tail_live_n)

    def _get_sharded_probe(self, base, gen: int, k: int, batched: bool):
        """Per-(generation, batched, k) ``make_sharded_pruned_probe``
        factory cache; the placed store is reused across k and batched."""
        from repro.core.histogram import make_sharded_pruned_probe

        with self._lock:
            if gen != self.generation:       # raced a swap: rebuild fresh
                base = self._base
                gen = self.generation
            key = (gen, batched, int(k))
            probe = self._probe_factories.get(key)
            if probe is None:
                probe = make_sharded_pruned_probe(
                    self.mesh, base, k=k, batched=batched, impl=self.impl,
                    interpret=self.interpret, store=self._placed)
                self._probe_factories[key] = probe
            return probe, base

    def probe(self, preds: np.ndarray, thresholds: np.ndarray, *,
              k: int = 1, need_topk: bool = True,
              scalar_kernel: bool = False
              ) -> tuple[np.ndarray, np.ndarray]:
        """Exact batched probe over live rows: base (pruned, live-masked)
        + hot tail (rowmask full scan), counts summed, top-k merged.

        preds (B, d); thresholds (B,) or (B, T). Returns (counts (B, T)
        int32, top-k (B, k) float32) — bitwise what a fresh full scan of
        the live rows returns for the same kernel shape
        (``scalar_kernel`` as in ``ClusteredStore.probe_pruned``).
        """
        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32)
        if thr.ndim == 1:
            thr = thr[:, None]
        b, t = thr.shape
        (base, gen, live, ls, base_live_n,
         temb, tlive, tail_live_n) = self._snapshot()
        n_live = base_live_n + tail_live_n
        k = max(1, min(int(k), max(n_live, 1)))
        counts = np.zeros((b, t), np.int64)
        cand = []
        if base_live_n:
            if self.mesh is not None:
                bc, bt = self._sharded_base_probe(
                    base, gen, preds, thr, k, need_topk, scalar_kernel,
                    live, ls)
            else:
                bc, bt, _ = base.probe_pruned(
                    preds, thr, k=k, impl=self.impl,
                    interpret=self.interpret, scalar_kernel=scalar_kernel,
                    need_topk=need_topk, live=live, live_sizes=ls[0])
            counts += np.asarray(bc, np.int64)
            cand.append(np.asarray(bt, np.float32))
        if tail_live_n:
            tc, tt = self._tail_probe(temb, tlive, preds, thr, k,
                                      scalar_kernel, need_topk)
            counts += np.asarray(tc, np.int64)
            cand.append(np.asarray(tt, np.float32))
        if need_topk and cand:
            merged = np.sort(np.concatenate(cand, axis=1), axis=1)
            if merged.shape[1] < k:
                merged = np.concatenate(
                    [merged, np.full((b, k - merged.shape[1]), np.inf,
                                     np.float32)], axis=1)
            topk = merged[:, :k]
        else:
            topk = np.full((b, k), np.inf, np.float32)
        return counts.astype(np.int32), topk

    def probe_compound(self, preds: np.ndarray, thresholds: np.ndarray, *,
                       mode: str = "and") -> tuple[int, dict]:
        """Exact compound match count over live rows: base compound probe
        (joint cluster bounds, live-masked) + compound rowmask tail scan,
        counts summed. Bitwise what composing fresh full scans of the live
        rows yields — per-row distances are row-local, so base/tail
        decomposition and tombstone masking never change a row's score.
        """
        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32).reshape(-1)
        (base, gen, live, ls, base_live_n,
         temb, tlive, tail_live_n) = self._snapshot()
        count = 0
        stats = None
        if base_live_n:
            if self.mesh is not None:
                rows = base.shard_rows
                live_l = [live[s * rows:(s + 1) * rows]
                          for s in range(base.n_shards)]
                c, stats = base.probe_compound(
                    preds, thr, mode=mode, live=live_l, live_sizes=ls,
                    live_n=[int(x.sum()) for x in ls])
            else:
                c, stats = base.probe_compound(preds, thr, mode=mode,
                                               live=live, live_sizes=ls[0])
            count += int(c)
        if tail_live_n:
            m = len(temb)
            bucket = max(128, 1 << max(0, m - 1).bit_length())
            emb_p = np.zeros((bucket, temb.shape[1]), np.float32)
            emb_p[:m] = temb
            mask = np.zeros(bucket, np.int32)
            mask[:m] = tlive
            count += int(_tail_compound_xla(
                jnp.asarray(emb_p), jnp.asarray(mask), jnp.asarray(preds),
                jnp.asarray(thr), mode=mode))
        return count, (stats or {"launches": 0, "rows_scanned": 0})

    def _sharded_base_probe(self, base, gen, preds, thr, k, need_topk,
                            scalar, live, ls):
        probe, base = self._get_sharded_probe(base, gen, k,
                                              batched=not scalar)
        rows = base.shard_rows
        live_l = [live[s * rows:(s + 1) * rows]
                  for s in range(base.n_shards)]
        live_n = [int(x.sum()) for x in ls]
        if scalar:
            c, tp = probe(preds[0], thr[0], need_topk=need_topk,
                          live=live_l, live_sizes=ls, live_n=live_n)
            return np.asarray(c)[None], np.asarray(tp)[None]
        c, tp = probe(preds, thr, need_topk=need_topk, live=live_l,
                      live_sizes=ls, live_n=live_n)
        return np.asarray(c), np.asarray(tp)

    def _tail_probe(self, temb, tlive, preds, thr, k, scalar, need_topk):
        """Rowmask full scan of the hot tail, kernel shape matched to the
        caller's (scalar VPU reduce vs batch MXU dot — the parity
        invariant); returns (counts (B, T), topk (B, k_t))."""
        m = len(temb)
        k_t = int(min(k, m)) if need_topk else 1
        if self.impl == "pallas":
            from repro.kernels.cosine_topk import ops as ct

            mask = jnp.asarray(tlive.astype(np.int32))
            store = jnp.asarray(temb)
            if scalar:
                c, tp = ct.cosine_probe_rowmask(
                    store, mask, jnp.asarray(preds[0]), jnp.asarray(thr[0]),
                    k=k_t, interpret=self.interpret)
                return np.asarray(c)[None], np.asarray(tp)[None]
            c, tp = ct.cosine_probe_batch_rowmask(
                store, mask, jnp.asarray(preds), jnp.asarray(thr), k=k_t,
                interpret=self.interpret)
            return np.asarray(c), np.asarray(tp)
        # xla twins: pad to a power-of-two bucket (dead mask rows) so the
        # jitted scans compile O(log tail) shapes as the tail grows
        bucket = max(128, 1 << (m - 1).bit_length())
        emb_p = np.zeros((bucket, temb.shape[1]), np.float32)
        emb_p[:m] = temb
        mask = np.zeros(bucket, np.int32)
        mask[:m] = tlive
        k_t = min(k_t, bucket)
        if scalar:
            c, tp = _tail_probe_xla(jnp.asarray(emb_p), jnp.asarray(mask),
                                    jnp.asarray(preds[0]),
                                    jnp.asarray(thr[0]), k=k_t)
            return np.asarray(c)[None], np.asarray(tp)[None]
        c, tp = _tail_probe_batch_xla(jnp.asarray(emb_p), jnp.asarray(mask),
                                      jnp.asarray(preds), jnp.asarray(thr),
                                      k=k_t)
        return np.asarray(c), np.asarray(tp)

    def kth_smallest(self, pred: np.ndarray, k: int, **_ignored) -> float:
        """Exact k-th smallest distance over live rows (scalar kernel
        shape, matching ``SemanticHistogram.kth_smallest_distance``)."""
        _, topk = self.probe(np.asarray(pred, np.float32)[None],
                             np.zeros((1, 1), np.float32), k=int(k),
                             need_topk=True, scalar_kernel=True)
        kk = max(1, min(int(k), topk.shape[1]))
        return float(topk[0, kk - 1])

    def count_bounds(self, preds: np.ndarray, thresholds: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Certified count interval over live rows, zero rows read: the
        base's live-masked bounds plus [0, tail_live] for the unindexed
        tail (a tail row can land anywhere relative to the threshold)."""
        with self._lock:
            base = self._base
            ls = [s.copy() for s in self._live_sizes]
            tail_live_n = self._tail_live_n
        if self.mesh is not None:
            lo, hi = base.count_bounds(preds, thresholds, live_sizes=ls)
        else:
            lo, hi = base.count_bounds(preds, thresholds, live_sizes=ls[0])
        return lo, hi + tail_live_n

    def distances(self, pred: np.ndarray) -> np.ndarray:
        """Distances of all live rows (base stored order, then tail order)
        — test/debug only, like ``SemanticHistogram.distances``."""
        with self._lock:
            rows = np.concatenate([self._base_emb_np[self._live],
                                   self._tail_emb[:self._tail_len]
                                   [self._tail_live[:self._tail_len]]])
        sims = jnp.matmul(jnp.asarray(rows).astype(f32),
                          jnp.asarray(pred, f32), precision=HIGHEST)
        return np.asarray(1.0 - sims)

    # ------------------------------------------------------------- rebuild

    def _due_locked(self) -> bool:
        n_live = self._base_live_n + self._tail_live_n
        if n_live == 0:
            return False
        n_base = len(self._live)
        if self._tail_live_n / n_live >= self.rebuild_tail_frac:
            return True
        if (n_base - self._base_live_n) / max(1, n_base) \
                >= self.rebuild_dead_frac:
            return True
        return self._max_inflation_locked() >= self.rebuild_inflation

    def _max_inflation_locked(self) -> float:
        worst = 1.0
        for (cs, _), sizes, tight in zip(self._segments, self._live_sizes,
                                         self._tight):
            ok = (sizes > 0) & (cs.radii > 1e-9)
            if ok.any():
                worst = max(worst, float(
                    (cs.radii[ok] / np.maximum(tight[ok], 1e-12)).max()))
        return worst

    def maybe_rebuild(self) -> bool:
        """Spawn a background rebuild if a trigger fired; False if not due
        or one is already running."""
        with self._lock:
            if self._rebuilding or not self._due_locked():
                return False
            self._rebuilding = True
            self._deleted_during_rebuild = set()
        self._rebuild_thread = threading.Thread(
            target=self._do_rebuild, name="mutable-index-rebuild",
            daemon=True)
        self._rebuild_thread.start()
        return True

    def drain_rebuild(self, timeout: float | None = None) -> None:
        """Join any in-flight background rebuild (no-op when idle). Call
        before process exit so the daemon builder isn't killed mid-swap."""
        with self._lock:
            t = self._rebuild_thread if self._rebuilding else None
        if t is not None and t is not threading.current_thread():
            t.join(timeout)

    def rebuild(self, *, wait: bool = True) -> bool:
        """Force a rebuild now (regardless of triggers). ``wait=False``
        runs it in the background. Returns False if one was already in
        flight (after joining it when ``wait``)."""
        with self._lock:
            if self._rebuilding:
                t = self._rebuild_thread
            else:
                self._rebuilding = True
                self._deleted_during_rebuild = set()
                t = None
        if t is not None:
            if wait:
                t.join()
            return False
        if wait:
            self._do_rebuild()
            return True
        self._rebuild_thread = threading.Thread(
            target=self._do_rebuild, name="mutable-index-rebuild",
            daemon=True)
        self._rebuild_thread.start()
        return True

    def _do_rebuild(self) -> bool:
        """Snapshot live rows -> build new base (outside the lock) -> swap.

        The new base covers every row live at snapshot time; mutations that
        land during the build are reconciled at swap: inserts stay in the
        (new) tail, deletes of snapshotted rows become tombstones in the
        new base. Sharded mode holds ``n % n_shards`` remainder rows back
        into the new tail so per-shard rows stay equal.
        """
        t0 = time.perf_counter()
        try:
            with self._lock:
                base_rows = np.flatnonzero(self._live)
                x_base = self._base_emb_np[base_rows]
                ids_base = self._base_ids[base_rows]
                snap_len = self._tail_len
                tpos = np.flatnonzero(self._tail_live[:snap_len])
                x_tail = self._tail_emb[tpos].copy()
                ids_tail = self._tail_ids[tpos].copy()
                prev_cent = None
                if self.incremental:
                    prev_cent = (self._base.global_centroids
                                 if self.mesh is not None
                                 else np.asarray(self._base.centroids))
                prev_loc = (dict(self._loc)
                            if self.mesh is not None and self.incremental
                            else None)
            x_new = np.concatenate([x_base, x_tail])
            ids_new = np.concatenate([ids_base, ids_tail])
            leftover_x = np.empty((0, self.d), np.float32)
            leftover_ids = np.empty(0, np.int64)
            if self.mesh is not None:
                r = len(x_new) % self._n_shards
                n_keep = len(x_new) - r
                if n_keep < self._n_shards:
                    return False          # too few live rows to shard-build
                if r:
                    leftover_x, leftover_ids = x_new[n_keep:], ids_new[n_keep:]
                    x_new, ids_new = x_new[:n_keep], ids_new[:n_keep]
                rows = n_keep // self._n_shards
                k_eff = max(1, min(self._k_clusters, rows))
                shard_hint = None
                if prev_loc is not None:
                    sr = self._base.shard_rows
                    shard_hint = np.full(len(ids_new), -1, np.int64)
                    for j, i in enumerate(ids_new):
                        loc = prev_loc.get(int(i))
                        if loc is not None and loc[0] == "b":
                            shard_hint[j] = loc[1] // sr
                init_c = (prev_cent if prev_cent is not None
                          and len(prev_cent) <= n_keep else None)
                new_base = build_sharded_clustered_store(
                    x_new, k_eff, self._n_shards,
                    iters=(self.rebuild_iters if init_c is not None
                           else self.iters),
                    seed=self.seed, impl=self.impl,
                    interpret=self.interpret, eps=self.eps,
                    chunk_rows=self.chunk_rows, balance="boundary",
                    split_radius=self.split_radius,
                    max_clusters=self._max_clusters,
                    init_centroids=init_c, shard_hint=shard_hint)
            else:
                if not len(x_new):
                    return False
                k_eff = max(1, min(self._k_clusters, len(x_new)))
                init_c = (prev_cent if prev_cent is not None
                          and len(prev_cent) <= len(x_new) else None)
                new_base = build_clustered_store(
                    x_new, k_eff,
                    iters=(self.rebuild_iters if init_c is not None
                           else self.iters),
                    seed=self.seed, impl=self.impl,
                    interpret=self.interpret, eps=self.eps,
                    chunk_rows=self.chunk_rows,
                    split_radius=self.split_radius,
                    max_clusters=self._max_clusters,
                    init_centroids=init_c)
            prepared = self._prepare_state(new_base, ids_new)
            hook = self._pre_swap_hook
            if hook is not None:
                hook()
            with self._lock:
                self._swap_locked(prepared, leftover_x, leftover_ids,
                                  snap_len)
                self.rebuilds += 1
                self.generation += 1
                self.version += 1
                self.last_rebuild_s = time.perf_counter() - t0
                self.last_rebuild_incremental = init_c is not None
                obs, gen = self._obs, self.generation
                rebuild_s = self.last_rebuild_s
            if obs is not None:
                obs.rebuild(seconds=rebuild_s,
                            incremental=init_c is not None,
                            generation=gen)
            return True
        finally:
            with self._lock:
                self._rebuilding = False
                self._deleted_during_rebuild = set()

    def _swap_locked(self, prepared: dict, leftover_x, leftover_ids,
                     snap_len: int) -> None:
        """Atomic generation swap (lock held): install the prepared base,
        re-apply mid-rebuild deletes as tombstones, rebuild the tail from
        mid-rebuild inserts + the sharded remainder rows."""
        dead = self._deleted_during_rebuild
        keep = [p for p in range(snap_len, self._tail_len)
                if self._tail_live[p]]
        tail_x = [self._tail_emb[p].copy() for p in keep]
        tail_ids = [int(self._tail_ids[p]) for p in keep]
        for xrow, i in zip(leftover_x, leftover_ids):
            if int(i) not in dead:
                tail_x.append(xrow)
                tail_ids.append(int(i))
        self._apply_state(prepared)
        for i in dead:
            loc = self._loc.pop(int(i), None)
            if loc is not None and loc[0] == "b":
                self._tombstone_pos(loc[1])
        self._reset_tail(
            np.asarray(tail_x, np.float32).reshape(-1, self.d),
            np.asarray(tail_ids, np.int64))

    # ----------------------------------------------------------- telemetry

    @property
    def obs(self):
        """Telemetry hub; assigning forwards it to the CURRENT base index
        (scan accounting lives there) and every rebuild's generation swap
        re-forwards it to the new base automatically."""
        return self._obs

    @obs.setter
    def obs(self, hub) -> None:
        with self._lock:
            self._obs = hub
            self._base.obs = hub

    # --------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            d = {
                "n_live": self._base_live_n + self._tail_live_n,
                "base_rows": int(len(self._live)),
                "base_live": int(self._base_live_n),
                "base_dead": int(len(self._live) - self._base_live_n),
                "tail_rows": int(self._tail_len),
                "tail_live": int(self._tail_live_n),
                "inserts": self.inserts,
                "deletes": self.deletes,
                "rebuilds": self.rebuilds,
                "generation": self.generation,
                "version": self.version,
                "rebuilding": self._rebuilding,
                "max_inflation": self._max_inflation_locked(),
                "last_rebuild_s": self.last_rebuild_s,
                "last_rebuild_incremental": self.last_rebuild_incremental,
            }
            base = self._base
        d["base_stats"] = base.stats()
        return d

    def reset_stats(self) -> None:
        with self._lock:
            self._base.reset_stats()
