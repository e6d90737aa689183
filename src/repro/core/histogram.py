"""The Semantic Histogram: an embedding store + threshold-probe (paper §2).

No buckets — the paper's design decision is to keep *all* embeddings (§2.1);
the store is a (N, d) matrix sharded over the data axes at pod scale. The
probe primitives are:

  * ``count_within(pred, thr)``        -> selectivity (§2.2 step 5)
  * ``kth_smallest_distance(pred, k)`` -> threshold calibration (§3.2)
  * ``probe_batch / selectivity_batch / kth_smallest_batch`` — the same two
    primitives for B predicates in **one** pass over the store: a query
    plan (or a serving fleet draining a queue of concurrent estimator
    calls) needs selectivity for many predicates at once, and streaming
    the store once per batch turns B bandwidth-bound matvecs into a single
    (N, d) x (d, B) MXU matmul — ~B× less HBM traffic per predicate.

All probes are a single pass over the store: ``impl="pallas"`` runs the
fused ``cosine_topk`` kernels (B-tiled for coalesced batches with
B >> 128; compiled on a TPU, interpreted elsewhere), ``impl="xla"`` the
jnp twins. Every distance contraction runs at ``Precision.HIGHEST``, so a
TPU's MXU keeps f32 accuracy. Distributed: each shard counts/top-ks
locally, then one tiny ``psum``/gather combines — the probe's collective
traffic is O(B*k), independent of N.

Cluster-pruned index (PR 3): construct with ``index=`` a
``repro.index.ClusteredStore`` built from the *same* embeddings and every
count/top-k probe routes through the pruned path — clusters whose exact
distance bounds put them entirely inside (or outside) the threshold are
counted (or skipped) without touching a row, and only boundary clusters are
scanned, by one masked-kernel launch per probe. Counts and top-k distances
stay exactly equal to the full scan (the bounds are conservative by
``index.eps``); at low selectivity the scan fraction collapses — see
``index.stats()``. ``kth_smallest_distance`` switches to bound-ordered
cluster scanning with early termination (§3.2 threshold calibration without
the full pass).

Sharded pruning (PR 4): at pod scale the two subsystems compose. Build a
``repro.index.ShardedClusteredStore`` (one k-means sub-index per contiguous
shard row-block) and construct with ``mesh=`` + ``index=``: every probe
plans all shards on the host (exact f64 Cauchy-Schwarz bounds per shard),
gathers only boundary segments into a per-shard bucket, and launches ONE
shard_map whose body scans the local bucket via the masked cosine_topk
kernels before the same O(B*k) psum/all-gather combine — bitwise equal to
the full-scan sharded path, a fraction of the rows per chip. ``mesh=``
without an index routes through ``make_sharded_probe`` (full scan, local
kernels + tiny collectives). Per-shard scan fractions: ``index.stats()``.

Serving layer (PR 2): ``probe_batch`` is cache-aware — construct with
``cache=PredicateCache(...)`` (see ``repro.launch.coalescer``; any object
with the same ``key``/``get``/``put`` surface works, the histogram only
duck-types it) and repeated predicates skip the store scan entirely: hits
are filled from the LRU, only the miss subset is probed, and the probe's
exact outputs are cached so a later hit is bitwise-identical to the fresh
probe. Cross-*query* batching lives one level up in
``repro.launch.coalescer.PredicateCoalescer``, which collects concurrent
``plan_query`` probes in a micro-batch window and drains them through this
``probe_batch`` in one kernel launch.

Compilation: the jitted probe entry points live at module level (plain
``jax.jit`` functions), so every ``SemanticHistogram`` instance shares one
trace cache keyed on (impl, k, shapes) — building many histograms (tests,
per-dataset serving stacks) no longer pays a retrace each.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cosine_topk.ref import HIGHEST

f32 = jnp.float32


def _local_probe(store, pred, thresholds, k):
    """store (n,d) f32/bf16; pred (d,); thresholds (t,). Returns
    (counts (t,), smallest_k (k,)) — one pass, fused."""
    sims = jnp.einsum("nd,d->n", store.astype(f32), pred.astype(f32),
                      precision=HIGHEST)
    dists = 1.0 - sims
    counts = (dists[None, :] <= thresholds[:, None]).sum(axis=1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


def _local_probe_batch(store, preds, thresholds, k):
    """store (n,d); preds (B,d); thresholds (B,t). Returns
    (counts (B,t), smallest_k (B,k)) — one store pass for all B predicates."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = 1.0 - sims                                      # (B, n)
    counts = (dists[:, None, :] <= thresholds[:, :, None]).sum(axis=-1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


def _masked_local_probe(store, n_valid, pred, thresholds, k):
    """``_local_probe`` over the first ``n_valid`` rows of a scan buffer.

    The einsum's dot reduction is row-local, so each valid row's distance is
    bitwise the distance ``_local_probe`` computes for that row in a full
    scan — the invariant the pruned sharded path's parity rests on. Dead
    rows score +inf (never counted, never in the top-k)."""
    sims = jnp.einsum("nd,d->n", store.astype(f32), pred.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(jnp.arange(store.shape[0]) < n_valid,
                      1.0 - sims, jnp.inf)
    counts = (dists[None, :] <= thresholds[:, None]).sum(axis=1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


def _masked_local_probe_batch(store, n_valid, preds, thresholds, k):
    """Batched twin of ``_masked_local_probe`` (mirrors the ``nd,bd->bn``
    contraction of ``_local_probe_batch`` so pruned batched scans stay
    bitwise the full batched scan's per-row distances)."""
    sims = jnp.einsum("nd,bd->bn", store.astype(f32), preds.astype(f32),
                      precision=HIGHEST)
    dists = jnp.where(jnp.arange(store.shape[0])[None, :] < n_valid,
                      1.0 - sims, jnp.inf)
    counts = (dists[:, None, :] <= thresholds[:, :, None]).sum(axis=-1)
    neg_top, _ = jax.lax.top_k(-dists, k)
    return counts, -neg_top


# XLA CPU vectorizes the einsum across rows but handles the trailing
# ``n % _ROW_QUANTUM`` rows with a separate remainder loop whose reduction
# order differs — the same row can score 1 ulp differently depending on its
# *position* relative to that boundary. Every decomposed scan path (pruned
# buckets, sharded buckets, the mutable base+tail twins) pads its buffer to
# an 8-aligned bucket, so their per-row distances are the stable main-loop
# values; a monolithic full scan over a misaligned store is the one place a
# remainder row can appear, and it would break bitwise parity with every
# decomposed path. ``_row_stable_store`` pads such stores (once, cached) to
# a _ROW_BUCKET multiple and scans them through the masked twins instead.
_ROW_QUANTUM = 8
_ROW_BUCKET = 128


# Module-level jitted probes: shared across every SemanticHistogram instance
# (jax.jit caches traces per (shapes, static k) on the *function object*, so
# hoisting out of __post_init__ removes the per-instance retrace).
@partial(jax.jit, static_argnames=("k",))
def _probe_xla(store, pred, thresholds, *, k: int):
    return _local_probe(store, pred, thresholds, k)


@partial(jax.jit, static_argnames=("k",))
def _probe_batch_xla(store, preds, thresholds, *, k: int):
    return _local_probe_batch(store, preds, thresholds, k)


@partial(jax.jit, static_argnames=("k",))
def _masked_probe_xla(store, n_valid, pred, thresholds, *, k: int):
    return _masked_local_probe(store, n_valid, pred, thresholds, k)


@partial(jax.jit, static_argnames=("k",))
def _masked_probe_batch_xla(store, n_valid, preds, thresholds, *, k: int):
    return _masked_local_probe_batch(store, n_valid, preds, thresholds, k)


@dataclasses.dataclass
class SemanticHistogram:
    embeddings: jax.Array        # (N, d) unit vectors
    mesh: object | None = None   # sharded probes when set
    impl: str = "xla"            # xla | pallas (interpret on CPU)
    cache: object | None = None  # PredicateCache-like (duck-typed)
    index: object | None = None  # ClusteredStore (single-device) or
    #                              ShardedClusteredStore (with mesh=)

    def __post_init__(self):
        self._n_static = self.embeddings.shape[0]
        self._sharded_probes = {}    # (pruned, batched, k) -> callable
        self._store_sharded = None   # lazily placed (full or reordered)
        self._store_row_stable = None  # lazily padded (see _ROW_QUANTUM)
        self._mutable = (self.index is not None
                         and getattr(self.index, "is_mutable", False))
        if self._mutable:
            # the mutable store owns its base index, tail, mesh placement
            # and probe dispatch; the histogram only routes to it, so the
            # static checks below don't apply — validate the wiring instead
            if self.index.mesh is not self.mesh:
                raise ValueError(
                    "a MutableClusteredStore carries its own mesh; pass "
                    "the same mesh (or None) to SemanticHistogram")
            if self.index.impl != self.impl:
                raise ValueError(
                    f"index impl {self.index.impl!r} != histogram impl "
                    f"{self.impl!r} — kernel shapes must match for "
                    f"bitwise parity")
            if self.index.d != self.embeddings.shape[1]:
                raise ValueError(
                    f"index dim {self.index.d} != store dim "
                    f"{self.embeddings.shape[1]}")
            return
        if self.mesh is not None:
            self._data_axes = _mesh_data_axes(self.mesh)
            n_shards = 1
            for a in self._data_axes:
                n_shards *= self.mesh.shape[a]
            self._n_shards = n_shards
            if self.n % n_shards:
                raise ValueError(
                    f"store rows ({self.n}) must divide the mesh's "
                    f"{n_shards} data shards evenly")
        if self.index is not None:
            sharded_index = hasattr(self.index, "shards")
            if sharded_index and self.mesh is None:
                raise ValueError(
                    "a ShardedClusteredStore index needs mesh=... (use "
                    "build_clustered_store for single-device probing)")
            if self.mesh is not None and not sharded_index:
                raise ValueError(
                    "mesh=... needs a ShardedClusteredStore index (use "
                    "build_sharded_clustered_store, one sub-index per "
                    "shard)")
            if sharded_index and self.index.n_shards != self._n_shards:
                raise ValueError(
                    f"index has {self.index.n_shards} shards, mesh has "
                    f"{self._n_shards} — rebuild the index for this mesh")
            if self.index.n != self.n:
                raise ValueError(
                    f"index holds {self.index.n} rows, store has {self.n} — "
                    f"build the ClusteredStore from the same embeddings")
            # spot-check content too: a stale index over same-shaped but
            # different embeddings would silently break exactness
            rows = [0, self.n // 2, self.n - 1] if self.n else []
            for i in rows:
                if not np.array_equal(
                        np.asarray(self.index.embeddings[i], np.float32),
                        np.asarray(self.embeddings[self.index.perm[i]],
                                   np.float32)):
                    raise ValueError(
                        "index embeddings disagree with the store — build "
                        "the ClusteredStore from the same embeddings")

    @property
    def n(self) -> int:
        """Row count the probe results are over: the live count for a
        mutable index (it changes under ingest), the store rows otherwise.
        Selectivity denominators and k clamps read this."""
        if self._mutable:
            return self.index.n_live
        return self._n_static

    @property
    def version(self) -> int:
        """Monotonic mutation counter (0 for immutable stores). Folded
        into predicate-cache keys so a cached count is never served across
        a mutation that may have changed it."""
        if self._mutable:
            return self.index.version
        return 0

    # -------------------- sharded routing --------------------

    def _sharded_probe(self, *, k: int, batched: bool):
        """Build-and-cache one sharded probe per (pruned, batched, k).

        Sharded probes always run the scan under shard_map with O(B*k)
        collectives; with a ShardedClusteredStore attached the scan is the
        pruned masked-kernel launch, bitwise equal to the full-scan sharded
        path for the same ``impl``."""
        key = (self.index is not None, batched, k)
        probe = self._sharded_probes.get(key)
        if probe is None:
            if self.index is not None:
                if self._store_sharded is None:
                    from jax.sharding import NamedSharding, PartitionSpec
                    self._store_sharded = jax.device_put(
                        self.index.embeddings,
                        NamedSharding(self.mesh,
                                      PartitionSpec(self._data_axes)))
                probe = make_sharded_pruned_probe(
                    self.mesh, self.index, k=k, batched=batched,
                    impl=self.impl, store=self._store_sharded)
            else:
                if self._store_sharded is None:
                    from jax.sharding import NamedSharding, PartitionSpec
                    self._store_sharded = jax.device_put(
                        self.embeddings,
                        NamedSharding(self.mesh,
                                      PartitionSpec(self._data_axes)))
                inner = jax.jit(make_sharded_probe(
                    self.mesh, k=k, batched=batched, impl=self.impl))
                store = self._store_sharded

                def probe(preds, thresholds, *, need_topk=True,
                          _inner=inner, _store=store):
                    return _inner(_store, jnp.asarray(preds),
                                  jnp.asarray(thresholds, f32))

            self._sharded_probes[key] = probe
        return probe

    # -------------------- core fused probe --------------------

    def _probe(self, pred: jax.Array, thresholds: jax.Array, *, k: int,
               need_topk: bool = True):
        if self._mutable:
            counts, topk = self.index.probe(
                np.asarray(pred, np.float32)[None],
                np.asarray(thresholds, np.float32)[None], k=k,
                need_topk=need_topk, scalar_kernel=True)
            return jnp.asarray(counts[0]), jnp.asarray(topk[0])
        if self.mesh is not None:
            counts, topk = self._sharded_probe(k=k, batched=False)(
                np.asarray(pred, np.float32),
                np.asarray(thresholds, np.float32), need_topk=need_topk)
            return jnp.asarray(counts), jnp.asarray(topk)
        if self.index is not None:
            # scalar_kernel: match the scalar full-scan kernel bitwise;
            # need_topk=False (count-only callers) lets a fully-resolved
            # probe skip the kernel launch entirely
            counts, topk, _ = self.index.probe_pruned(
                np.asarray(pred, np.float32)[None],
                np.asarray(thresholds, np.float32)[None], k=k,
                impl=self.impl, scalar_kernel=True, need_topk=need_topk)
            return jnp.asarray(counts[0]), jnp.asarray(topk[0])
        if self.impl == "pallas":
            from repro.kernels.cosine_topk import ops as ct

            return ct.cosine_probe(self.embeddings, pred, thresholds, k=k)
        store = self._row_stable_store()
        if store is self.embeddings:
            return _probe_xla(store, pred, thresholds, k=k)
        return _masked_probe_xla(store, jnp.int32(self._n_static), pred,
                                 thresholds, k=k)

    def _probe_batched(self, preds: jax.Array, thresholds: jax.Array, *,
                       k: int, need_topk: bool = True):
        if self._mutable:
            counts, topk = self.index.probe(
                np.asarray(preds, np.float32),
                np.asarray(thresholds, np.float32), k=k,
                need_topk=need_topk)
            return jnp.asarray(counts), jnp.asarray(topk)
        if self.mesh is not None:
            counts, topk = self._sharded_probe(k=k, batched=True)(
                np.asarray(preds, np.float32),
                np.asarray(thresholds, np.float32), need_topk=need_topk)
            return jnp.asarray(counts), jnp.asarray(topk)
        if self.index is not None:
            counts, topk, _ = self.index.probe_pruned(
                np.asarray(preds, np.float32),
                np.asarray(thresholds, np.float32), k=k, impl=self.impl,
                need_topk=need_topk)
            return jnp.asarray(counts), jnp.asarray(topk)
        if self.impl == "pallas":
            from repro.kernels.cosine_topk import ops as ct

            return ct.cosine_probe_batch(self.embeddings, preds, thresholds,
                                         k=k)
        store = self._row_stable_store()
        if store is self.embeddings:
            return _probe_batch_xla(store, preds, thresholds, k=k)
        return _masked_probe_batch_xla(store, jnp.int32(self._n_static),
                                       preds, thresholds, k=k)

    def _row_stable_store(self):
        """``self.embeddings``, row-padded (zero rows, masked to +inf by
        the masked twins) whenever ``n % _ROW_QUANTUM != 0`` so no real
        row lands in the XLA remainder loop — the parity anchor every
        decomposed scan (pruned / sharded / mutable base+tail) matches.
        Aligned stores (every production-sized one) scan as-is, zero copy."""
        if self._store_row_stable is None:
            n = self._n_static
            if n % _ROW_QUANTUM == 0:
                self._store_row_stable = self.embeddings
            else:
                pad = (-n) % _ROW_BUCKET
                self._store_row_stable = jnp.concatenate(
                    [self.embeddings,
                     jnp.zeros((pad, self.embeddings.shape[1]),
                               self.embeddings.dtype)])
        return self._store_row_stable

    # -------------------- public API (scalar) --------------------

    def count_within(self, pred: np.ndarray, threshold: float) -> int:
        counts, _ = self._probe(
            jnp.asarray(pred), jnp.asarray([threshold], f32), k=1,
            need_topk=False,
        )
        return int(counts[0])

    def selectivity(self, pred: np.ndarray, threshold: float) -> float:
        return self.count_within(pred, threshold) / self.n

    def count_compound(self, preds: np.ndarray, thresholds: np.ndarray, *,
                       mode: str = "and") -> int:
        """Exact match count of a conjunction ("and") / disjunction ("or")
        of per-predicate threshold filters, in one pass.

        preds (B, d) are the B conjuncts of ONE compound predicate,
        thresholds (B,) their per-conjunct thresholds. With an index
        attached the joint cluster-bound pass resolves most clusters with
        zero rows read and ONE masked launch scores the surviving boundary
        union; the result is bitwise-equal to composing per-predicate full
        scans (the canonical batched XLA contraction — compound row sets
        cannot route through the Pallas kernels, which return only counts
        and top-k, never per-row masks).
        """
        if mode not in ("and", "or"):
            raise ValueError(f"mode must be 'and' or 'or', got {mode!r}")
        preds_np = np.asarray(preds, np.float32)
        thr_np = np.asarray(thresholds, np.float32).reshape(-1)
        if self._mutable:
            count, _ = self.index.probe_compound(preds_np, thr_np,
                                                 mode=mode)
            return int(count)
        if self.index is not None:
            count, _ = self.index.probe_compound(preds_np, thr_np,
                                                 mode=mode)
            return int(count)
        from repro.index.clustered import _compound_masked_xla

        store = self._row_stable_store()
        return int(_compound_masked_xla(
            store, jnp.int32(self._n_static), jnp.asarray(preds_np),
            jnp.asarray(thr_np), mode=mode))

    def selectivity_compound(self, preds: np.ndarray,
                             thresholds: np.ndarray, *,
                             mode: str = "and") -> float:
        """Compound selectivity: ``count_compound / n`` over live rows."""
        return self.count_compound(preds, thresholds, mode=mode) \
            / max(self.n, 1)

    def kth_smallest_distance(self, pred: np.ndarray, k: int) -> float:
        k = max(1, min(k, self.n))
        if self._mutable:
            return self.index.kth_smallest(pred, int(k))
        if self.mesh is not None:
            # sharded calibration: one thr=0 probe — each shard contributes
            # its exact local top-min(k, shard_rows) (pruned: via the top-k
            # cover), and the O(k) combine resorts, so topk[k-1] is the
            # exact global k-th, bitwise the full-pass value
            _, smallest = self._probe(
                jnp.asarray(pred), jnp.zeros((1,), f32), k=int(k))
            return float(smallest[k - 1])
        if self.index is not None:
            # bound-ordered cluster scan, early-terminated — same value as
            # the full pass, a fraction of the rows
            return self.index.kth_smallest(pred, int(k), impl=self.impl)
        _, smallest = self._probe(
            jnp.asarray(pred), jnp.zeros((1,), f32), k=int(k)
        )
        return float(smallest[k - 1])

    # -------------------- public API (batched) --------------------

    def probe_batch(self, preds: np.ndarray, thresholds: np.ndarray, *,
                    k: int = 1, use_cache: bool = True,
                    need_topk: bool = True,
                    ) -> tuple[jax.Array, jax.Array]:
        """One fused pass for B predicates. preds (B, d); thresholds (B,)
        or (B, T). Returns (counts (B, T) int32, top-k distances (B, k)).

        When a ``cache`` is attached (and ``use_cache``), each predicate is
        looked up by quantized (embedding, thresholds, k) key first; only
        the miss subset hits the kernel, and its exact outputs are cached.
        The coalescer passes ``use_cache=False`` — it consults the same
        cache at submit time, so flushes must not double-count lookups.

        ``need_topk=False`` (count-only callers that discard the top-k)
        lets a pruned-index probe skip its top-k cluster cover — the
        returned top-k is then unspecified. Ignored on the cached path:
        cached values must stay exact for every future key-equal caller."""
        preds = jnp.asarray(preds)
        thr = jnp.asarray(thresholds, f32)
        if thr.ndim == 1:
            thr = thr[:, None]
        k = max(1, min(int(k), self.n))
        if self.cache is None or not use_cache:
            return self._probe_batched(preds, thr, k=k, need_topk=need_topk)
        return self._probe_batched_cached(np.asarray(preds, np.float32),
                                          np.asarray(thr), k=k)

    def _probe_batched_cached(self, preds: np.ndarray, thr: np.ndarray, *,
                              k: int) -> tuple[jax.Array, jax.Array]:
        """Fill hits from the LRU, probe only the misses, cache the rest.

        The miss subset is padded (repeating rows) to a power-of-two bucket
        <= B before probing, so the jitted probe compiles O(log B) shapes
        instead of one per distinct miss count."""
        b, t = thr.shape
        ver = self.version
        keys = [self.cache.key(preds[j], thr[j], k, version=ver)
                for j in range(b)]
        hits = [self.cache.get(key) for key in keys]
        miss = [j for j, h in enumerate(hits) if h is None]
        counts = np.empty((b, t), np.int32)
        topk = np.empty((b, k), np.float32)
        for j, h in enumerate(hits):
            if h is not None:
                counts[j], topk[j] = h
        if miss:
            bucket = min(b, 1 << (len(miss) - 1).bit_length())
            rows = miss + [miss[-1]] * (bucket - len(miss))
            mc, mt = self._probe_batched(jnp.asarray(preds[rows]),
                                         jnp.asarray(thr[rows]), k=k)
            mc, mt = np.asarray(mc), np.asarray(mt)
            for i, j in enumerate(miss):
                counts[j], topk[j] = mc[i], mt[i]
                self.cache.put(keys[j], (mc[i].copy(), mt[i].copy()))
        return jnp.asarray(counts), jnp.asarray(topk)

    def selectivity_batch(self, preds: np.ndarray,
                          thresholds: np.ndarray) -> np.ndarray:
        """Selectivity of B (predicate, threshold) pairs via one store pass —
        one device round-trip for the whole batch."""
        counts, _ = self.probe_batch(preds, thresholds, k=1, need_topk=False)
        return np.asarray(counts[:, 0]) / self.n

    def selectivity_bounds(self, preds: np.ndarray, thresholds: np.ndarray
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Certified selectivity interval per predicate — zero rows read.

        Returns (lo, hi), each (B,) float64 with lo <= true selectivity
        <= hi. With a cluster index attached the interval comes from the
        index's exact Cauchy-Schwarz count bounds (``count_bounds``);
        without one the only certified interval is the trivial [0, 1].
        The serving layer answers from this when the scan path is
        unavailable (overload, open breaker) — degraded but never wrong.
        """
        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32).reshape(-1)
        if preds.ndim != 2 or preds.shape[0] != thr.shape[0]:
            raise ValueError(f"preds {preds.shape} vs thresholds "
                             f"{thr.shape}")
        if self.index is not None:
            lo, hi = self.index.count_bounds(preds, thr)
            return lo[:, 0] / self.n, hi[:, 0] / self.n
        b = preds.shape[0]
        return np.zeros(b, np.float64), np.ones(b, np.float64)

    def kth_smallest_batch(self, preds: np.ndarray, k: int) -> np.ndarray:
        """k-th smallest distance per predicate, (B,) float — batched
        threshold calibration."""
        k = max(1, min(int(k), self.n))
        b = np.asarray(preds).shape[0]
        _, smallest = self.probe_batch(preds, np.zeros((b,), np.float32), k=k)
        return np.asarray(smallest[:, k - 1])

    def distances(self, pred: np.ndarray) -> np.ndarray:
        """Full distance vector — test/debug only (not the serving path).
        For a mutable index: distances of the *live* rows."""
        if self._mutable:
            return self.index.distances(pred)
        sims = jnp.matmul(self.embeddings.astype(f32), jnp.asarray(pred, f32),
                          precision=HIGHEST)
        return np.asarray(1.0 - sims)


def _mesh_data_axes(mesh) -> tuple[str, ...]:
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not axes:
        raise ValueError(f"mesh {dict(mesh.shape)} has no 'pod'/'data' axis "
                         f"to shard the store over")
    return axes


def make_sharded_probe(mesh, *, k: int = 128, batched: bool = False,
                       impl: str = "xla", interpret: bool | None = None):
    """shard_map probe over a ('pod','data')-sharded store: local fused pass,
    psum of counts, all-gather + resort of per-shard top-k. Used by the probe
    scaling benchmark and the multi-pod serve path.

    Scalar (default): pred (d,), thresholds (T,) -> (counts (T,), top (k,)).
    ``batched=True``: preds (B, d), thresholds (B, T) -> (counts (B, T),
    top (B, k)) — psum of the (B, T) counts, all-gather of the per-shard
    (B, k) top-k along a fresh shard axis, then a per-predicate resort.
    Collective traffic stays O(B*k), independent of the store size.

    ``impl='pallas'`` scans each shard with the fused cosine_topk kernels
    (interpret mode on CPU) instead of the jnp einsum — the kernel-shape
    twin the pruned sharded path (``make_sharded_pruned_probe``) must match
    for bitwise parity. Each shard's local top-k is clamped to its row
    count, so ``k`` may exceed the per-shard rows (threshold calibration
    asks for k up to N); the merged result is still the exact global top-k.
    """
    from jax.sharding import PartitionSpec as P

    data_axes = _mesh_data_axes(mesh)

    def _scan(store, preds, thresholds, kk):
        if impl == "pallas":
            from repro.kernels.cosine_topk import ops as ct

            if preds.ndim == 2:
                return ct.cosine_probe_batch(store, preds, thresholds, k=kk,
                                             interpret=interpret)
            return ct.cosine_probe(store, preds, thresholds, k=kk,
                                   interpret=interpret)
        if preds.ndim == 2:
            return _local_probe_batch(store, preds, thresholds, kk)
        return _local_probe(store, preds, thresholds, kk)

    def probe(store, pred, thresholds):
        kk = min(k, store.shape[0])
        counts, local_top = _scan(store, pred, thresholds, kk)
        counts = jax.lax.psum(counts, data_axes)
        gathered = jax.lax.all_gather(local_top, data_axes, tiled=True)
        return counts, -jax.lax.top_k(-gathered,
                                      min(k, gathered.shape[0]))[0]

    def probe_batch(store, preds, thresholds):
        kk = min(k, store.shape[0])
        counts, local_top = _scan(store, preds, thresholds, kk)
        counts = jax.lax.psum(counts, data_axes)
        # (nshards, B, kk) -> (B, nshards*kk) -> per-predicate resort
        gathered = jax.lax.all_gather(local_top, data_axes)
        flat = jnp.moveaxis(gathered, 0, 1).reshape(local_top.shape[0], -1)
        return counts, -jax.lax.top_k(-flat, min(k, flat.shape[1]))[0]

    return jax.shard_map(
        probe_batch if batched else probe, mesh=mesh,
        in_specs=(P(data_axes), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )


def make_sharded_pruned_probe(mesh, index, *, k: int = 128,
                              batched: bool = False, impl: str = "xla",
                              interpret: bool | None = None, store=None):
    """Cluster-pruned twin of ``make_sharded_probe`` — sublinear per shard.

    ``index`` is a ``repro.index.ShardedClusteredStore`` whose shard blocks
    match the mesh's ('pod','data') row partition. The returned
    ``probe(preds, thresholds, need_topk=True)`` plans every shard on the
    host (exact f64 Cauchy-Schwarz bounds — x64 is off inside traces, and
    the plan is O(S*K*B) host flops), gathers each shard's boundary-union
    segments into one power-of-two bucket, and launches ONE shard_map whose
    body scans only its local bucket through the masked cosine_topk kernels
    (``impl='pallas'``) or their jnp twins (``impl='xla'``), then runs the
    same O(B*k) psum / all-gather combine as the full-scan path. Counts and
    top-k are bitwise equal to ``make_sharded_probe`` with the same
    ``impl`` — all-in/all-out clusters are resolved by bounds (eps covers
    the f32 kernel roundoff), and the per-shard top-k cover keeps each
    shard's local top-k exact.

    The bucket is uniform across shards (shard_map needs one shape), so
    the launch costs max-over-shards boundary rows per chip — uneven
    boundary work shows up in ``index.stats()['per_shard']``, not in
    correctness. Bucket sizes are power-of-two, so the jit compiles
    O(log shard_rows) shapes per (k, batched). ``need_topk=False``
    (count-only callers) skips the top-k cover; a probe whose every cluster
    resolves by bounds then launches nothing at all and the returned top-k
    is +inf. ``store`` overrides the pre-placed reordered store (it must be
    ``index.embeddings`` under the mesh's data sharding); by default it is
    placed here once per factory.

    The gather and the scan are two separate device dispatches on purpose:
    fused into one program, XLA folds the segment gather into the distance
    contraction and is then free to re-associate the dot's reduction —
    the per-row distances drift an ulp from the full scan's and bitwise
    parity dies (optimization_barrier does not stop it). Materializing the
    per-shard buckets between two shard_maps pins the scan's operand, the
    same reason ``ClusteredStore._gather`` runs its gather
    (``index.clustered.gather_rows``) as a program apart from the jitted
    masked probe.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    data_axes = _mesh_data_axes(mesh)
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    if n_shards != index.n_shards:
        raise ValueError(
            f"index has {index.n_shards} shards but the mesh's data axes "
            f"hold {n_shards} devices — rebuild the index for this mesh")
    kk = max(1, min(int(k), index.shard_rows))   # per-shard cover / gather
    k_final = max(1, min(int(k), index.n))
    if store is None:
        store = jax.device_put(index.embeddings,
                               NamedSharding(mesh, P(data_axes)))

    def gather_rows(store_l, idx_l):    # jit_gather_rows on the trace
        return jnp.take(store_l, idx_l[0], axis=0)

    gather = jax.jit(jax.shard_map(
        gather_rows, mesh=mesh, in_specs=(P(data_axes), P(data_axes)),
        out_specs=P(data_axes), check_vma=False,
    ))

    def body(buf, nv_l, extra_l, preds, thr):
        if impl == "pallas":
            from repro.kernels.cosine_topk import ops as ct

            if batched:
                counts, top = ct.cosine_probe_batch_masked(
                    buf, nv_l[0], preds, thr, k=kk, interpret=interpret)
            else:
                counts, top = ct.cosine_probe_masked(
                    buf, nv_l[0], preds, thr, k=kk, interpret=interpret)
        elif batched:
            counts, top = _masked_local_probe_batch(buf, nv_l[0], preds,
                                                    thr, kk)
        else:
            counts, top = _masked_local_probe(buf, nv_l[0], preds, thr, kk)
        counts = jax.lax.psum(counts.astype(jnp.int32) + extra_l[0],
                              data_axes)
        if batched:
            gathered = jax.lax.all_gather(top, data_axes)   # (S, B, kk)
            flat = jnp.moveaxis(gathered, 0, 1).reshape(top.shape[0], -1)
            return counts, -jax.lax.top_k(-flat, k_final)[0]
        flat = jax.lax.all_gather(top, data_axes, tiled=True)   # (S*kk,)
        return counts, -jax.lax.top_k(-flat, k_final)[0]

    sharded = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axes), P(data_axes), P(data_axes), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    ))

    def probe(preds, thresholds, *, need_topk: bool = True, live=None,
              live_sizes=None, live_n=None):
        """``live`` (per-shard (rows,) bool masks), ``live_sizes``
        (per-shard (K_s,) live cluster counts) and ``live_n`` (per-shard
        live totals) thread the mutable store's tombstones through: plans
        run over live sizes, gathers drop dead rows, and the stats
        denominator is the live row count. All three default to the static
        (everything-live) behavior."""
        preds = np.asarray(preds, np.float32)
        thr = np.asarray(thresholds, np.float32)
        if batched and thr.ndim == 1:
            thr = thr[:, None]
        p2 = preds if batched else preds[None, :]
        t2 = thr if batched else thr[None, :]
        b, t = t2.shape
        plans = index.plan_shards(p2, t2, k=kk, need_topk=need_topk,
                                  live_sizes=live_sizes)
        m_max = max(p.m for p in plans)
        if m_max == 0:              # every cluster on every shard resolved
            counts = np.sum([p.extra for p in plans],
                            axis=0).astype(np.int32)        # (B, T)
            top = np.full((b, k_final), np.inf, np.float32)
            index.record(plans, launched=False, live_n=live_n)
            return (counts, top) if batched else (counts[0], top[0])
        if live is None and all(p.m == index.shard_rows for p in plans):
            # every shard promoted to a full scan (high selectivity prunes
            # nothing): the store itself is the buffer — no gather copy,
            # exactly the worst case of the full-scan path and no more.
            # Disabled under tombstones: dead rows must never be scanned.
            buf = store
            bucket = index.shard_rows
            nv = np.full(n_shards, index.shard_rows, np.int32)
        else:
            bucket = min(max(128, 1 << (max(m_max, kk) - 1).bit_length()),
                         index.shard_rows)
            idx = np.zeros((n_shards, bucket), np.int32)
            nv = np.zeros(n_shards, np.int32)
            for s, plan in enumerate(plans):
                if plan.m:
                    idx[s, :plan.m] = index.shards[s].scan_rows(
                        plan.scan_ids,
                        live=None if live is None else live[s])
                    nv[s] = plan.m
            buf = gather(store, jnp.asarray(idx))   # (S*bucket, d) sharded
        extra = np.stack([p.extra.astype(np.int32) for p in plans])
        if not batched:
            extra = extra[:, 0, :]                          # (S, T)
        counts, top = sharded(buf, jnp.asarray(nv), jnp.asarray(extra),
                              jnp.asarray(preds), jnp.asarray(thr))
        index.record(plans, launched=True, live_n=live_n,
                     gathered=[bucket] * n_shards)
        return np.asarray(counts), np.asarray(top)

    return probe
