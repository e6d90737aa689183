#!/usr/bin/env python3
"""Smoke run of the semantic-histogram serving path on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the sharded probes over four chips

One chip: builds the serving stack with ``repro.launch.serve.build_stack``
over the wildlife catalog at 1,048,576 rows x 1152 dims (f32, 4.8 GB of
HBM) three times — XLA probes, Pallas probes, and the Pallas probes behind
a 1024-cluster pruned index — and through each serves 8 queries x 3
filters at concurrency 8, two passes, with ``serve_concurrent``. Each
phase checks probe counts against a float64 NumPy full scan on the host,
for 64 predicates with thresholds spread from 0.01% to 50% selectivity:
equal, except for rows whose float64 distance lies within 1e-4 of the
threshold. Top-16 and 1000-th smallest distances must agree within 1e-4.
The pruned index also answers count-only probes for 8 catalog rows at
thresholds four rows wide, one predicate at a time: these must prune (scan
fraction below 1), so the cluster gather and the batch kernel masked to a
prefix of its bucket run on the chip, and must match the same reference.

``--chips 4`` runs only the sharded full-scan probe
(``make_sharded_probe``) and the sharded pruned probe
(``build_sharded_clustered_store``) over the same rows split across four
chips, against the same reference, the catalog-row probes included.

The last line of standard output is a JSON object with ``ok`` and the
device JAX reports. The script fails — without that line — when the
default backend is not a TPU, on any failed query, parity mismatch or
exception. The data comes from ``--seed``. JAX's compilation cache is kept
where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_ROWS = 1 << 20          # 1,048,576 catalog rows
K_CLUSTERS = 1024         # sqrt(N): docs/index.md
N_PREDS = 64
TOL = 1e-4
TOP_K = 16
CALIB_K = 1000            # above the kernel's in-VMEM top-k selection cap


def log(msg: str) -> None:
    print(msg, flush=True)


class Reference:
    """Float64 full scan on the host: per predicate, the counts a probe
    may return (rows within TOL of the threshold may fall either way) and
    the exact smallest distances."""

    def __init__(self, images, preds, targets):
        import numpy as np

        p64 = preds.astype(np.float64)
        dist = np.empty((len(preds), len(images)), np.float64)
        for s in range(0, len(images), 65536):
            dist[:, s:s + 65536] = 1.0 - p64 @ images[s:s + 65536].astype(
                np.float64).T
        n = len(images)
        thr = np.empty(len(preds), np.float64)
        for j, q in enumerate(targets):
            r = max(1, int(round(q * n)))
            part = np.partition(dist[j], (r - 1, r))
            thr[j] = 0.5 * (part[r - 1] + part[r])
        self.thr = thr.astype(np.float32)
        t = self.thr.astype(np.float64)[:, None]
        self.lo = (dist < t - TOL).sum(axis=1)
        self.hi = (dist <= t + TOL).sum(axis=1)
        self.exact = (dist <= t).sum(axis=1)
        self.top = np.sort(np.partition(dist, TOP_K, axis=1)[:, :TOP_K],
                           axis=1)
        self.kth = np.partition(dist[:4], CALIB_K - 1,
                                axis=1)[:, CALIB_K - 1]

    def check(self, name, counts, top=None, kth=None) -> bool:
        """``top=None``: a count-only probe, whose top-k is unspecified."""
        import numpy as np

        counts = np.asarray(counts).reshape(-1)
        bad = np.flatnonzero((counts < self.lo) | (counts > self.hi))
        off = int(np.abs(counts - self.exact).sum())
        near = int((self.hi - self.lo).sum())
        msg = (f"parity {name}: {len(counts)} predicates, counts outside "
               f"the +-{TOL} band {len(bad)}, |count - f64 count| summed "
               f"{off} (rows within {TOL} of a threshold: {near})")
        ok = not len(bad)
        if top is not None:
            top_err = float(np.max(np.abs(np.asarray(top)[:, :TOP_K]
                                          - self.top)))
            msg += f", top-{TOP_K} max |err| {top_err:.3g}"
            ok = ok and top_err <= TOL
        if kth is not None:
            kth_err = float(np.max(np.abs(np.asarray(kth) - self.kth)))
            msg += f", {CALIB_K}-th distance max |err| {kth_err:.3g}"
            ok = ok and kth_err <= TOL
        log(msg + (" OK" if ok else " MISMATCH"))
        return ok


def predicates(corpus, seed):
    """64 predicate embeddings from generate_queries, thresholds spread
    log-uniformly from 0.01% to 50% selectivity."""
    import numpy as np

    from repro.core.optimizer import generate_queries

    nodes: list[int] = []
    for q in generate_queries(corpus, n_queries=4 * N_PREDS, n_filters=3,
                              seed=seed):
        nodes.extend(int(x) for x in q if int(x) not in nodes)
    nodes = (nodes * N_PREDS)[:N_PREDS]
    preds = np.stack([corpus.text_embedding(nid, seed) for nid in nodes])
    return preds, np.geomspace(1e-4, 0.5, N_PREDS)


def catalog_rows(images, seed):
    """8 catalog rows as predicates and their float64 reference at a
    threshold four rows wide. Few clusters can hold such a match, so the
    index prunes: a batch of generate_queries predicates, or any probe
    that needs its top-k, covers nearly every cluster of this catalog and
    is promoted to a full scan, which gathers nothing."""
    import numpy as np

    rows = np.random.default_rng(seed).choice(N_ROWS, 8, replace=False)
    preds = images[rows]
    return preds, Reference(images, preds, np.full(len(rows), 4 / N_ROWS))


def pruning(name, ref, preds, probe, index) -> bool:
    """Count-only probes, one predicate at a time, through the pruned
    index. Fails unless they prune: at a scan fraction of 1 the index
    scans the store in place, and the gather and masked kernel never
    run."""
    import numpy as np

    index.reset_stats()
    counts = [np.asarray(probe(preds[j:j + 1], ref.thr[j:j + 1])[0])
              for j in range(len(preds))]
    frac = index.stats()["scan_fraction"]
    log(f"[{name}] {len(preds)} count-only catalog-row probes: scan "
        f"fraction {frac:.4f}")
    ok = ref.check(f"{name} pruning", np.concatenate(
        [c.reshape(-1) for c in counts]))
    if frac >= 1.0:
        log(f"[{name}] the probes did not prune: the gather and masked "
            f"kernel did not run")
    return ok and frac < 1.0


def hbm(dev) -> tuple[int, int, int]:
    st = dev.memory_stats() or {}
    return (st.get("bytes_in_use", 0), st.get("bytes_limit", 0),
            st.get("peak_bytes_in_use", 0))


def reckon(dev, name: str, copies: int, d: int) -> None:
    """Refuse a phase whose store copies cannot fit next to what is
    already on the device."""
    used, limit, _ = hbm(dev)
    need = copies * N_ROWS * d * 4
    log(f"[{name}] device bytes before: {used / 1e9:.2f} GB in use; phase "
        f"needs ~{need / 1e9:.2f} GB ({copies} store copies) of "
        f"{limit / 1e9:.2f} GB")
    if limit and used + need > limit:
        raise RuntimeError(f"{name}: {need} bytes do not fit next to "
                           f"{used} in use (limit {limit})")


def kernel_programs(d: int) -> None:
    """The probe and k-means programs the served path compiles hold Mosaic
    kernels, not an interpreter loop."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.cosine_topk import ops as ct
    from repro.kernels.kmeans.kernel import assign_blocks

    f32 = jnp.float32
    store = jax.ShapeDtypeStruct((N_ROWS, d), f32)
    progs = {
        "probe": ct.cosine_probe_batch.lower(
            store, jax.ShapeDtypeStruct((24, d), f32),
            jax.ShapeDtypeStruct((24, 1), f32), k=1),
        "masked probe": ct.cosine_probe_batch_masked.lower(
            store, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((24, d), f32),
            jax.ShapeDtypeStruct((24, 1), f32), k=1),
        "k-means": assign_blocks.lower(
            store, jax.ShapeDtypeStruct((K_CLUSTERS, d), f32)),
    }
    for name, lowered in progs.items():
        t0 = time.perf_counter()
        found = "tpu_custom_call" in lowered.compile().as_text()
        log(f"kernel {name}: compiled in {time.perf_counter() - t0:.2f}s, "
            f"tpu_custom_call {'found' if found else 'MISSING'}")
        if not found:
            raise RuntimeError(f"{name} program has no Mosaic kernel")


def serve_phase(dev, name, impl, clusters, args, corpus, ref, preds,
                rows) -> bool:
    import numpy as np

    from repro.core.optimizer import generate_queries
    from repro.launch.serve import build_stack, serve_concurrent
    from repro.obs import ObsHub

    reckon(dev, name, 3 if clusters else 1, preds.shape[1])
    t0 = time.perf_counter()
    _, ests = build_stack("wildlife", seed=args.seed, impl=impl,
                          index_clusters=clusters, corpus=corpus)
    hist = ests["specificity"].hist
    log(f"[{name}] set-up (store, index, specificity model, kv-batch "
        f"store): {time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    counts, top = hist.probe_batch(preds, ref.thr, k=TOP_K)
    counts = np.asarray(counts)
    log(f"[{name}] first probe (compile + run): "
        f"{time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    kth = [hist.kth_smallest_distance(preds[j], CALIB_K) for j in range(4)]
    log(f"[{name}] {CALIB_K}-th distance x4: "
        f"{time.perf_counter() - t0:.2f}s")
    ok = ref.check(name, counts, top, kth)
    if hist.index is not None:
        row_preds, row_ref = rows
        ok = pruning(name, row_ref, row_preds, lambda p, t: hist.probe_batch(
            p, t, use_cache=False, need_topk=False), hist.index) and ok

    hub = ObsHub()
    if hist.index is not None:
        hist.index.obs = hub
        hist.index.reset_stats()
    queries = generate_queries(corpus, n_queries=8, n_filters=3,
                               seed=args.seed)
    serve_concurrent(corpus, ests, queries, est_name="ensemble",
                     seed=args.seed, concurrency=8, window_ms=4.0,
                     max_batch=64, cache_size=1024, cache_bits=12, passes=2,
                     obs=hub)
    reg = hub.registry
    failed = reg.counter("serve.failed_queries").value
    plan = reg.histogram("serve.plan_ms")
    log(f"[{name}] serve: {reg.counter('serve.queries').value} queries in "
        f"{reg.gauge('serve.wall_s').value:.3f}s; plan wall per query p50 "
        f"{plan.percentile(50):.2f} ms, p95 {plan.percentile(95):.2f} ms, "
        f"max {plan.percentile(100):.2f} ms")
    half = len(plan.values()) // 2
    log(f"[{name}] plan wall p50 of the first {half} plans to finish "
        f"{np.percentile(plan.values()[:half], 50):.2f} ms, of the rest "
        f"{np.percentile(plan.values()[half:], 50):.2f} ms")
    log(f"[{name}] inside the plans, per predicate request (p50 / p95 "
        f"ms): " + ", ".join(
            f"{ph} {reg.histogram(f'serve.{ph}_ms').percentile(50):.2f} / "
            f"{reg.histogram(f'serve.{ph}_ms').percentile(95):.2f}"
            for ph in ("queue_wait", "probe", "combine", "request")))
    log(f"[{name}] serve.failed_queries = {failed}")
    if hist.index is not None:
        st = hist.index.stats()
        log(f"[{name}] index: {hist.index.k_clusters} clusters, "
            f"scan fraction {st['scan_fraction']:.4f} over "
            f"{st['probes']} probes")
    log(f"[{name}] peak device bytes so far: {hbm(dev)[2] / 1e9:.2f} GB")
    return ok and failed == 0


def one_chip(args, dev) -> bool:
    import jax

    from repro.configs import get_config
    from repro.core.synthetic import make_corpus

    t0 = time.perf_counter()
    corpus = make_corpus("wildlife", n_images=N_ROWS, seed=args.seed)
    d = corpus.dim
    log(f"rows {N_ROWS}, d {d}, K {K_CLUSTERS} (catalog built in "
        f"{time.perf_counter() - t0:.2f}s)")
    preds, targets = predicates(corpus, args.seed)
    t0 = time.perf_counter()
    ref = Reference(corpus.images, preds, targets)
    log(f"float64 reference over {N_PREDS} predicates: "
        f"{time.perf_counter() - t0:.2f}s; selectivity "
        f"{ref.exact.min() / N_ROWS:.2e}..{ref.exact.max() / N_ROWS:.2e}")
    rows = catalog_rows(corpus.images, args.seed)
    kernel_programs(d)

    ok = True
    for name, impl, clusters in (("xla", "xla", 0), ("pallas", "pallas", 0),
                                 ("pruned", "pallas", K_CLUSTERS)):
        ok = serve_phase(dev, name, impl, clusters, args, corpus, ref,
                         preds, rows) and ok
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        log(f"[{name}] released: {live / 1e9:.2f} GB of live arrays left")
    full = get_config("llava-next-8b", smoke=False)
    cut = get_config("llava-next-8b", smoke=True)
    log(f"kv-batch VLM ran at reduced width: d_model {cut.d_model} (published "
        f"{full.d_model}), {cut.num_layers} layers (published "
        f"{full.num_layers}), vocab {cut.vocab_size} (published "
        f"{full.vocab_size})")
    return ok


def four_chips(args, devs) -> bool:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.histogram import (
        make_sharded_probe,
        make_sharded_pruned_probe,
    )
    from repro.core.synthetic import make_corpus
    from repro.index import build_sharded_clustered_store
    from repro.launch.mesh import make_probe_mesh

    shards = 4
    corpus = make_corpus("wildlife", n_images=N_ROWS, seed=args.seed)
    per_shard_k = int(np.sqrt(N_ROWS // shards))
    log(f"rows {N_ROWS}, d {corpus.dim}, {shards} shards of "
        f"{N_ROWS // shards}, K {per_shard_k} per shard")
    preds, targets = predicates(corpus, args.seed)
    ref = Reference(corpus.images, preds, targets)
    row_preds, row_ref = catalog_rows(corpus.images, args.seed)
    mesh = make_probe_mesh(shards)
    rows = NamedSharding(mesh, P("data"))

    store = jax.device_put(corpus.images, rows)
    log("full store shards on: " + ", ".join(
        f"{s.device} rows {s.index[0].start}:{s.index[0].stop}"
        for s in store.addressable_shards))
    probe = jax.jit(make_sharded_probe(mesh, k=TOP_K, batched=True,
                                       impl="pallas"))
    t0 = time.perf_counter()
    counts, top = probe(store, preds, ref.thr[:, None])
    counts = np.asarray(counts)
    log(f"sharded full probe (compile + run): "
        f"{time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    jax.block_until_ready(probe(store, preds, ref.thr[:, None]))
    log(f"sharded full probe (warm): {time.perf_counter() - t0:.4f}s")
    ok = ref.check("sharded full", counts, top)
    del store

    t0 = time.perf_counter()
    index = build_sharded_clustered_store(corpus.images, per_shard_k, shards,
                                          seed=args.seed, impl="pallas")
    log(f"sharded index build: {time.perf_counter() - t0:.2f}s")
    placed = jax.device_put(index.embeddings, rows)
    log("pruned store shards on: " + ", ".join(
        str(s.device) for s in placed.addressable_shards))
    pruned = make_sharded_pruned_probe(mesh, index, k=TOP_K, batched=True,
                                       impl="pallas", store=placed)
    t0 = time.perf_counter()
    counts, top = pruned(preds, ref.thr)
    log(f"sharded pruned probe (compile + run): "
        f"{time.perf_counter() - t0:.2f}s")
    ok = ref.check("sharded pruned", counts, top) and ok
    ok = pruning("sharded pruned", row_ref, row_preds,
                 lambda p, t: pruned(p, t, need_topk=False), index) and ok
    st = index.stats()
    log("pruned scan fraction per shard: " + ", ".join(
        f"{p['scan_fraction']:.4f}" for p in st["per_shard"]))
    log("peak device bytes: " + ", ".join(
        f"{d}: {hbm(d)[2] / 1e9:.2f} GB" for d in devs[:shards]))
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (default backend "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.serve import use_compile_cache

    log(f"device {devs[0].device_kind} x {len(devs)}; compile cache "
        f"{use_compile_cache()}")
    t0 = time.perf_counter()
    ok = one_chip(args, devs[0]) if args.chips == 1 else \
        four_chips(args, devs)
    log(f"total {time.perf_counter() - t0:.1f}s; peak device bytes "
        f"{hbm(devs[0])[2] / 1e9:.2f} GB (device 0)")
    if not ok:
        log("chip_smoke: FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
