"""The benchmark's own image catalog, drawn from a seed.

Copied from ``src/repro/core/synthetic.py`` (``make_corpus`` presets,
``_build_tree``, ``Corpus.predicate_nodes`` and ``Corpus.text_embedding``),
so that a change to the program cannot move the yardstick. Departures:

* the images are drawn in f32 on the device, ``CHUNK_ROWS`` at a time,
  instead of a leaf at a time on the host;
* the concept tree comes from a fixed seed of its own (``TREE_SEED``),
  so every catalog of a preset has the same concepts at the same
  selectivities, whatever the seed of the images;
* every random stream comes from a ``numpy.random.SeedSequence``, so any
  whole number up to 2**63 is a distinct seed.

The result is wrapped in the program's ``Corpus`` type, which is what
``repro.launch.serve.build_stack(corpus=...)`` takes. ``Catalog`` keeps
the raw arrays (directions, noise, images) that the float64 reference
reads: the reference computes predicate embeddings with
``text_embedding`` below, never with the program's method.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

# name -> tree shape, cluster tightness, modality gap, VLM error, leaf skew
# (src/repro/core/synthetic.py, make_corpus)
PRESETS = {
    "wildlife": dict(depth=4, branching=(2, 3), jitter=[0.6, 0.45, 0.35, 0.3],
                     img_noise=0.25, text_noise=0.18, vlm_error=0.08,
                     skew=1.6),
    "artwork": dict(depth=5, branching=(2, 3),
                    jitter=[0.7, 0.5, 0.45, 0.4, 0.35],
                    img_noise=0.45, text_noise=0.3, vlm_error=0.05, skew=1.2),
    "ecommerce": dict(depth=3, branching=(3, 5), jitter=[0.8, 0.5, 0.35],
                      img_noise=0.15, text_noise=0.12, vlm_error=0.03,
                      skew=2.2),
}


@dataclasses.dataclass
class Catalog:
    preset: str
    dim: int
    images: np.ndarray          # (N, d) f32 unit rows, leaf-contiguous
    directions: dict            # node id -> (d,) f64 unit direction
    depth: dict                 # node id -> depth
    children: dict              # node id -> [child ids]
    leaves: list                # leaf node ids, in row order
    counts: np.ndarray          # rows per leaf
    text_noise: float
    vlm_error: float
    seed: int

    @property
    def n(self) -> int:
        return int(self.images.shape[0])

    def text_embedding(self, node_id: int, paraphrase: int) -> np.ndarray:
        """A predicate's text embedding: the node's direction, a modality
        gap and noise; ``paraphrase`` is the phrasing (a new one gives a new
        embedding). Same arithmetic as ``Corpus.text_embedding``."""
        g = np.random.default_rng((node_id + 1) * 7919 + paraphrase)
        v = (self.directions[node_id] + self.text_noise
             * g.standard_normal(self.dim) / np.sqrt(self.dim))
        return (v / np.linalg.norm(v)).astype(np.float32)

    def predicate_nodes(self, max_per_depth: int = 8) -> list[int]:
        """Up to ``max_per_depth`` nodes of every depth, shuffled from the
        seed: a spread of predicates from broad to specific (the paper's
        protocol, ``Corpus.predicate_nodes``)."""
        rng = np.random.default_rng(
            np.random.SeedSequence(self.seed).spawn(3)[2])
        by_depth: dict[int, list[int]] = {}
        for nid, dep in self.depth.items():
            by_depth.setdefault(dep, []).append(nid)
        out = []
        for dep in sorted(by_depth):
            nodes = sorted(by_depth[dep])
            rng.shuffle(nodes)
            out.extend(nodes[:max_per_depth])
        return out

    def subtree_rows(self, node_id: int) -> np.ndarray:
        """Row ids of every image under ``node_id``."""
        ends = np.cumsum(self.counts)
        start = dict(zip(self.leaves, ends - self.counts))
        stop = dict(zip(self.leaves, ends))
        parts, todo = [], [node_id]
        while todo:
            nid = todo.pop()
            if nid in start:
                parts.append(np.arange(start[nid], stop[nid], dtype=np.int64))
            todo.extend(self.children[nid])
        return np.sort(np.concatenate(parts)) if parts else \
            np.empty(0, np.int64)


def _build_tree(rng, dim, depth, branching, jitter):
    """Concept tree: each child is its parent's direction plus jitter
    (``synthetic._build_tree``)."""
    scale = 1.0 / np.sqrt(dim)
    root = rng.standard_normal(dim)
    directions = {0: root / np.linalg.norm(root)}
    depths, children = {0: 0}, {0: []}
    frontier, next_id = [0], 1
    for d in range(1, depth + 1):
        new_frontier = []
        for pid in frontier:
            for _ in range(rng.integers(branching[0], branching[1] + 1)):
                v = directions[pid] + jitter[d - 1] * scale * \
                    rng.standard_normal(dim)
                directions[next_id] = v / np.linalg.norm(v)
                depths[next_id], children[next_id] = d, []
                children[pid].append(next_id)
                new_frontier.append(next_id)
                next_id += 1
        frontier = new_frontier
    return directions, depths, children, frontier


CHUNK_ROWS = 1 << 17      # rows per device draw: 0.6 GB at d = 1152
TREE_SEED = 0             # the concept tree of every catalog


def _draw_images_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key, leaf_dirs, leaf_of_row, noise_scale):
        """One chunk of f32 unit rows: each row its leaf's direction plus
        isotropic noise, normalised. Returned 128 wide when the chunk
        allows: the device's (8, 128) tiles are then already row-major,
        so the copy to the host needs no relayout."""
        z = jax.random.normal(key, (leaf_of_row.shape[0], leaf_dirs.shape[1]),
                              jnp.float32)
        v = leaf_dirs[leaf_of_row] + noise_scale * z
        v = v * jax.lax.rsqrt(jnp.sum(v * v, axis=1, keepdims=True))
        return v.reshape(-1, 128) if v.size % 128 == 0 else v

    return draw


def make_catalog(preset: str, rows: int, dim: int, seed: int) -> Catalog:
    """The catalog of ``preset`` at ``rows`` x ``dim`` from ``seed``.
    Leaf sizes are Zipf-skewed (``skew``); rows are grouped by leaf.

    ``TREE_SEED`` fixes the concept tree (its shape, directions and which
    leaf gets which Zipf weight); ``seed`` draws the leaf counts and the
    images."""
    import jax

    p = PRESETS[preset]
    count_ss, image_ss, _ = np.random.SeedSequence(seed).spawn(3)
    shape = np.random.default_rng(np.random.SeedSequence(TREE_SEED))
    directions, depths, children, leaves = _build_tree(
        shape, dim, p["depth"], p["branching"], p["jitter"])
    w = 1.0 / np.arange(1, len(leaves) + 1) ** p["skew"]
    shape.shuffle(w)
    counts = np.random.default_rng(count_ss).multinomial(rows, w / w.sum())
    key = jax.random.wrap_key_data(
        np.asarray(image_ss.generate_state(2), np.uint32))
    leaf_dirs = np.stack([directions[c] for c in leaves]).astype(np.float32)
    leaf_of_row = np.repeat(np.arange(len(leaves), dtype=np.int32), counts)
    t0 = time.perf_counter()
    draw = _draw_images_fn()
    draw(jax.random.fold_in(key, 0), leaf_dirs,
         leaf_of_row[:CHUNK_ROWS], np.float32(0)).block_until_ready()
    t_compile = time.perf_counter() - t0
    scale = np.float32(p["img_noise"] / np.sqrt(dim))
    starts = range(0, rows, CHUNK_ROWS)
    chunks = [draw(jax.random.fold_in(key, i), leaf_dirs,
                   leaf_of_row[s:s + CHUNK_ROWS], scale)
              for i, s in enumerate(starts)]
    for c in chunks:
        c.block_until_ready()
    t_draw = time.perf_counter() - t0 - t_compile
    images = np.empty((rows, dim), np.float32)
    for s, c in zip(starts, chunks):
        images[s:s + CHUNK_ROWS] = np.asarray(c).reshape(-1, dim)
    del chunks, c
    print(f"catalog: first chunk (compile) {t_compile:.2f}s, drawn "
          f"{t_draw:.2f}s, copied to the host "
          f"{time.perf_counter() - t0 - t_compile - t_draw:.2f}s",
          flush=True)
    return Catalog(preset=preset, dim=dim, images=images,
                   directions=directions, depth=depths, children=children,
                   leaves=list(leaves), counts=counts,
                   text_noise=p["text_noise"], vlm_error=p["vlm_error"],
                   seed=int(seed))


def as_corpus(cat: Catalog):
    """The catalog in the program's ``Corpus`` type."""
    from repro.core.synthetic import Concept, Corpus

    concepts = {}
    for nid, dep in cat.depth.items():
        parent = next((p for p, ch in cat.children.items() if nid in ch),
                      None)
        concepts[nid] = Concept(nid, dep, parent, list(cat.children[nid]),
                                cat.directions[nid], f"n{nid}",
                                cat.subtree_rows(nid))
    image_leaf = np.repeat(np.asarray(cat.leaves, np.int64), cat.counts)
    return Corpus(name=cat.preset, dim=cat.dim, images=cat.images,
                  image_leaf=image_leaf, concepts=concepts,
                  text_noise=cat.text_noise, vlm_error=cat.vlm_error,
                  rng=np.random.default_rng(
                      np.random.SeedSequence(cat.seed).spawn(4)[3]))
